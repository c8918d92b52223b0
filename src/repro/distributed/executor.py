"""Sharded spectral inference: execute a ``ShardedNetworkPlan`` under
``shard_map``.

The paper's Alg 1 answers "reuse kernels or activations?" per layer; a
multi-chip mesh re-asks it one level up (DESIGN.md §4), and the
two-level autotuner (``autotune.autotune_layer_sharded``) answers with
a partitioning strategy per layer.  This module is the runtime for that
answer — one ``shard_map`` per sharded layer, strategies mixing freely
across layers because every layer's output returns to a well-defined
global layout:

  channel   shard d owns input channels [d*M/D, (d+1)*M/D).  The full
      activation is replicated; each shard slices its channels by
      ``axis_index``, runs the fused kernel on its SLICED operands
      (stacked on a leading device axis, ``P(axis)``) producing a
      partial spatial sum, and a ring all-reduce (``lax.psum``) — the
      2(D-1)/D output bytes the cost model charges — combines them.
      Bias+ReLU were DEFERRED at plan build (a partial sum through a
      ReLU is wrong); the executor applies the base epilogue post-psum.

  spatial   shard d owns a contiguous band of tile rows.  Each shard
      ships its LAST k-1 raw rows to its lower neighbour
      (``lax.ppermute`` — the (D-1)*(k-1)*W*C bytes the cost model
      charges), prepends the received halo (zeros on shard 0 — exactly
      the global 'same' zero padding), and runs the band kernel
      (``kernels.fused_spectral_conv.execute_band_plan``) whose
      geometry's ``pre_halo_h`` accounts for the received rows.  Band
      canvases concatenate on H (``P(None, None, axis, None)``) and the
      'same' crop runs ONCE, globally.

  replicate every device executes the base plan on the full, replicated
      activation: a ``shard_map`` with replicated specs and no
      collective (a Mosaic kernel cannot be partitioned automatically,
      so even a replicated kernel over a mesh runs under ``shard_map``).
      Also the terminal rung of the sharded degradation ladder
      (``resilience.harden_sharded_plan``) — any layer that cannot run
      its fused shard kernels falls back here, a uniform plan-level
      decision, so no device is ever left blocked in a collective.

Every ``shard_map`` runs with ``check_vma=False`` — the bodies launch
Pallas kernels, which carry no replication rule.  The channel
strategy's stacked operands (``ShardedLayerPlan.operands``) are placed
on the mesh when the plan is built for it, so each device keeps its own
slice of the kernel resident.  The shard-scoped fault site
``shard_tables`` is consulted HOST-SIDE (operand staging), never inside
a shard_map body: per-device python control flow does not exist there
(one trace serves all devices), and host-side is precisely what turns
an injected shard fault into a structured error *before* any device
enters a collective.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding

from repro.core import resilience as res
from repro.core import spectral as spec
from repro.distributed import sharding as shd

Array = jax.Array


def _check_mesh(slp, mesh, axis: str) -> None:
    if axis not in mesh.axis_names:
        raise ValueError(f"mesh axes {mesh.axis_names} lack the plan's "
                         f"shard axis {axis!r}")
    size = mesh.shape[axis]
    if size != slp.n_shards:
        raise ValueError(
            f"layer {slp.base.layer.name}: plan was built for "
            f"{slp.n_shards} shards but mesh axis {axis!r} has {size} "
            f"devices — rebuild the plan for this mesh "
            f"(plans never port across topologies; see plan_cache_key)")


def _stage_shard_tables(slp, strategy: str):
    """Host-side staging of per-shard Alg-2 tables with the shard-scoped
    fault site applied (check + corrupt) — the one place a single
    shard's tables can fail or rot before the collective launches."""
    name = slp.base.layer.name
    staged = []
    for d, sh in enumerate(slp.shards):
        res.fault_check("shard_tables", layer=name, shard=d,
                        strategy=strategy)
        tb = sh.tables
        if tb is not None:
            tb = res.fault_corrupt("shard_tables", tb, layer=name,
                                   shard=d, strategy=strategy)
        staged.append(tb)
    return staged


def _defer_epilogue(lp):
    """A copy of ``lp`` whose in-kernel ReLU is suppressed (and any
    residual marker cleared): residual DAG nodes apply
    ``relu(y + shortcut)`` AFTER the collective, so the kernel must
    flush the bias-only activation."""
    return dataclasses.replace(
        lp, epilogue=dataclasses.replace(lp.epilogue, relu=False,
                                         residual=None))


def _execute_spatial(x: Array, slp, mesh, axis: str,
                     interpret: bool | None,
                     defer_relu: bool = False) -> Array:
    from repro.kernels.fused_spectral_conv import execute_band_plan

    base = slp.base
    geo = base.geo
    ov = geo.ksize - 1
    D = slp.n_shards
    band = slp.shards[0]
    staged = _stage_shard_tables(slp, "spatial")
    band = dataclasses.replace(band, tables=staged[0])
    if defer_relu:
        band = _defer_epilogue(band)
    hb = band.geo.n_tiles_h * geo.tile          # raw rows per shard
    xp = jnp.pad(x, ((0, 0), (0, 0), (0, D * hb - x.shape[2]), (0, 0)))

    def body(xb):
        # ship my last k-1 raw rows DOWN the mesh; shard 0's halo stays
        # zero — identical to the global 'same' zero padding.
        halo = jax.lax.ppermute(
            xb[:, :, -ov:, :], axis,
            [(i, i + 1) for i in range(D - 1)])
        x_ext = jnp.concatenate([halo, xb], axis=2)
        return execute_band_plan(x_ext, band, interpret=interpret)

    sp_ = shd.spectral_specs("spatial", axis)
    f = jax.shard_map(body, mesh=mesh, in_specs=sp_["x"],
                      out_specs=sp_["out"], check_vma=False)
    canvas = f(xp)                               # [B, N, D*hb, w_pad]
    return spec.crop_canvas_same(canvas, geo)


def _execute_channel(x: Array, slp, mesh, axis: str,
                     interpret: bool | None,
                     defer_relu: bool = False) -> Array:
    from repro.core.plan import PlanTables, stack_shard_operands
    from repro.kernels.fused_spectral_conv import execute_layer_plan

    base = slp.base
    shards = slp.shards
    mloc = shards[0].layer.c_in
    staged = _stage_shard_tables(slp, "channel")
    ops = slp.operands
    if any(st is not sh.tables for st, sh in zip(staged, shards)):
        ops = stack_shard_operands(shards, staged)   # a fault rewrote them
    # a no-op for operands the plan already placed on this mesh
    wr, wi, *tabs = jax.device_put(
        ops, NamedSharding(mesh, shd.spectral_stacked_spec(axis)))

    def body(xf, wrd, wid, *tb):
        i = jax.lax.axis_index(axis)
        xloc = jax.lax.dynamic_slice_in_dim(xf, i * mloc, mloc, 1)
        lp = dataclasses.replace(
            shards[0], wr=wrd[0], wi=wid[0],
            tables=PlanTables(*(t[0] for t in tb)) if tb else None)
        y = execute_layer_plan(xloc, lp, interpret=interpret)
        return jax.lax.psum(y, axis)

    sp_ = shd.spectral_specs("channel", axis)
    f = jax.shard_map(
        body, mesh=mesh,
        in_specs=(sp_["x"],) + (sp_["operand"],) * (2 + len(tabs)),
        out_specs=sp_["out"], check_vma=False)
    y = f(x, wr, wi, *tabs)
    # deferred bias(+ReLU) — a partial sum through a ReLU is wrong
    epi = _defer_epilogue(base) if defer_relu else base
    return res._spatial_epilogue(y, epi)


def _execute_replicated(x: Array, lp, mesh, interpret: bool | None,
                        shortcut: Array | None = None) -> Array:
    """The base plan on every device of ``mesh``, on the replicated
    activation (and shortcut): no collective, replicated output."""
    rep = shd.spectral_replicated_spec()

    def body(xf, *sc):
        return res.execute_planned_layer(xf, lp, interpret=interpret,
                                         shortcut=sc[0] if sc else None)

    args = (x,) if shortcut is None else (x, shortcut)
    f = jax.shard_map(body, mesh=mesh, in_specs=(rep,) * len(args),
                      out_specs=rep, check_vma=False)
    return f(*args)


def execute_sharded_layer(x: Array, slp, mesh, *,
                          axis: str = shd.SPECTRAL_AXIS,
                          interpret: bool | None = None,
                          defer_relu: bool = False) -> Array:
    """Run one conv layer of a ``ShardedNetworkPlan`` on ``mesh``.

    Dispatches on ``slp.strategy`` (see module doc).  The output is
    always the full [B, N, H_out, W_out] activation in the global
    layout, so consecutive layers may use different strategies.
    Pooling and stride subsampling stay with the caller (they are
    spatial and global), exactly as for
    ``resilience.execute_planned_layer``.

    ``defer_relu`` suppresses the epilogue ReLU wherever it would run
    (in-kernel, band kernel, or post-psum) and returns the bias-only
    activation — the residual DAG walk applies ``relu(y + shortcut)``
    after the collective.
    """
    if slp.strategy == "replicate" or not slp.shards:
        base = _defer_epilogue(slp.base) if defer_relu else slp.base
        return _execute_replicated(x, base, mesh, interpret)
    _check_mesh(slp, mesh, axis)
    if slp.strategy == "spatial":
        return _execute_spatial(x, slp, mesh, axis, interpret,
                                defer_relu)
    if slp.strategy == "channel":
        return _execute_channel(x, slp, mesh, axis, interpret,
                                defer_relu)
    raise ValueError(f"unknown shard strategy {slp.strategy!r}")


def _pool(x: Array, kind: str = "max") -> Array:
    b, c, h, w = x.shape
    h2, w2 = h // 2, w // 2
    x = x[:, :, :h2 * 2, :w2 * 2].reshape(b, c, h2, 2, w2, 2)
    return x.max(axis=(3, 5)) if kind == "max" else x.mean(axis=(3, 5))


def forward_spectral_sharded(params: dict, splan, x: Array, *,
                             mesh: Any | None = None,
                             interpret: bool | None = None) -> Array:
    """Sharded analogue of ``models.cnn.forward_spectral``.

    Walks the BASE plan's execution DAG (ISSUE 10) node by node:
    conv nodes run through ``execute_sharded_layer`` (strategies mix
    freely), pool nodes run globally, stride-2 outputs subsample after
    the collective, and residual edges add in the global layout —
    in-kernel (fused epilogue) only on replicated residual-FUSED
    layers, as a post-collective ``relu(y + shortcut)`` everywhere
    else.  The FC head runs replicated — the paper's CPU-side stage, a
    few matmuls XLA replicates trivially.  ``mesh`` defaults to
    ``launch.mesh.make_spectral_mesh(splan.n_shards, splan.axis)``.
    """
    if mesh is None:
        from repro.launch.mesh import make_spectral_mesh
        mesh = make_spectral_mesh(splan.n_shards, splan.axis)
    from repro.core.plan import graph_sink
    graph = splan.base.execution_graph
    out_id = graph_sink(graph)
    refs: dict[str, int] = {out_id: 1}
    for node in graph:
        for src in (node.inputs[0], node.residual_from):
            if src is not None:
                refs[src] = refs.get(src, 0) + 1
    acts: dict[str, Array] = {"input": x}
    for node in graph:
        src = acts[node.inputs[0]]
        if node.kind == "pool":
            y = _pool(src, node.pool)
        else:
            slp = splan.layers[node.layer_index]
            base = slp.base
            stride = getattr(base.layer, "stride", 1)
            sc = (acts[node.residual_from]
                  if node.residual_from is not None else None)
            replicated = slp.strategy == "replicate" or not slp.shards
            if sc is None:
                y = execute_sharded_layer(src, slp, mesh,
                                          axis=splan.axis,
                                          interpret=interpret)
                y = y[:, :, ::stride, ::stride]
            elif (replicated
                  and getattr(base, "backend", "fused") == "fused"
                  and getattr(base.epilogue, "residual", None)
                  == "fused"):
                # replicated residual-FUSED node: the shortcut rides
                # the kernel's epilogue flush (stride 1 by invariant)
                y = _execute_replicated(src, base, mesh, interpret,
                                        shortcut=sc)
            else:
                y = execute_sharded_layer(src, slp, mesh,
                                          axis=splan.axis,
                                          interpret=interpret,
                                          defer_relu=True)
                y = y[:, :, ::stride, ::stride] + sc
                if node.relu:
                    y = jax.nn.relu(y)
        acts[node.id] = y
        for s in (node.inputs[0], node.residual_from):
            if s is not None:
                refs[s] -= 1
                if refs[s] == 0:
                    acts.pop(s, None)
    from repro.models.cnn import fc_head
    return fc_head(params, acts[out_id])
