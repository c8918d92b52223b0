"""Spectral VGG16 — the paper's own target model, end to end.

Conv stack runs in the spectral domain (overlap-save FFT tiling + sparse
Hadamard, repro.core.spectral) with per-layer dataflow chosen by Alg 1;
max-pool / FC head run in the spatial domain.  On the paper's CPU+FPGA
platform those stages were offloaded to the CPU; here everything is one
jitted JAX program (DESIGN.md, adaptation note 3).

Since the LayerPlan refactor the forward pass *executes a plan*
(``core.plan.build_network_plan``): geometry, pruned kernels, Alg-2
active-bin compaction, autotuned flow/blocks and the fused bias+ReLU
epilogue are all precompiled once, offline — exactly as the paper
compiles per-layer configurations before inference — and every backend
of ``forward_spectral`` just walks the plan.
"""

from __future__ import annotations

import dataclasses
from typing import Sequence

import jax
import jax.numpy as jnp

from repro.core import dataflow as df
from repro.core import resilience as res
from repro.core import spans
from repro.core import sparse as sp
from repro.core import spectral as spec
from repro.models import layers as L

Array = jax.Array

# after which conv layers a 2x2 max-pool follows
_POOL_AFTER = frozenset(
    {"conv1_2", "conv2_2", "conv3_3", "conv4_3", "conv5_3"})


@dataclasses.dataclass(frozen=True)
class SpectralCNNConfig:
    """``graph`` (ISSUE 10) is an optional tuple of
    ``dataflow.NodeSpec`` describing a DAG over the conv layers —
    stride-2 convs, 2x2 max/avg pool nodes and residual shortcut edges
    (ResNet-class).  ``None`` keeps the linear VGG semantics: a chain
    of the layers with max-pools after ``pool_after``."""

    name: str = "vgg16-spectral"
    layers: Sequence[df.ConvLayer] = df.VGG16_LAYERS
    fft_size: int = 8
    # Spectral kernel compression: scalar, or one alpha per conv layer
    # (the paper prunes layers non-uniformly).
    alpha: float | Sequence[float] = 4.0
    n_classes: int = 1000
    image_size: int = 224
    fc_dim: int = 4096
    pool_after: frozenset = _POOL_AFTER
    graph: Sequence[df.NodeSpec] | None = None


def _config_graph(cfg: SpectralCNNConfig):
    """The topo-ordered NodeSpec sequence a config describes (explicit
    ``cfg.graph``, or the synthesized linear chain)."""
    from repro.core import plan as pl
    specs = cfg.graph
    if specs is None:
        specs = pl._linear_node_specs(
            list(cfg.layers), getattr(cfg, "pool_after", frozenset()))
    return pl._topo_order_specs(specs)


def feature_dim(cfg: SpectralCNNConfig) -> int:
    """Flattened feature size entering the FC head: the output shape of
    the graph's sink node (shape-walked, so stride/pool/DAG configs all
    agree with what the conv stack actually emits)."""
    from repro.core import plan as pl
    order = _config_graph(cfg)
    shapes = pl.node_output_shapes(list(cfg.layers), order)
    c, h, w = shapes[pl.graph_sink(order)]
    return c * h * w


def init(key, cfg: SpectralCNNConfig) -> dict:
    """Spatial-domain weights; spectral transform + pruning are separate
    (mirrors the paper: kernels pruned offline, stored pre-transformed)."""
    ks = jax.random.split(key, len(cfg.layers) + 3)
    convs = []
    for k, layer in zip(ks, cfg.layers):
        fan_in = layer.c_in * layer.ksize ** 2
        w = jax.random.normal(
            k, (layer.c_out, layer.c_in, layer.ksize, layer.ksize),
            jnp.float32) * (2.0 / fan_in) ** 0.5
        convs.append({"w": w, "b": jnp.zeros((layer.c_out,))})
    return {
        "convs": convs,
        "fc1": L.dense_init(ks[-3], feature_dim(cfg), cfg.fc_dim),
        "fc2": L.dense_init(ks[-2], cfg.fc_dim, cfg.fc_dim),
        "fc3": L.dense_init(ks[-1], cfg.fc_dim, cfg.n_classes),
    }


def transform_kernels(params: dict, cfg: SpectralCNNConfig
                      ) -> list[sp.SparseSpectralKernels]:
    """Offline: spatial -> spectral -> pruned, per-layer alpha."""
    alphas = sp.per_layer_alphas(cfg.alpha, len(cfg.layers))
    out = []
    for conv, alpha in zip(params["convs"], alphas):
        wf = spec.spectral_kernel(conv["w"], cfg.fft_size)
        out.append(sp.prune_magnitude(wf, alpha))
    return out


def build_plan(params: dict, cfg: SpectralCNNConfig, **kwargs):
    """Convenience re-export: ``core.plan.build_network_plan``."""
    from repro.core.plan import build_network_plan
    return build_network_plan(params, cfg, **kwargs)


def _pool(x: Array, kind: str = "max") -> Array:
    """2x2 stride-2 max/avg pool; odd edge rows/cols are dropped
    (floor semantics, mirrored by ``plan.node_output_shapes``)."""
    b, c, h, w = x.shape
    h2, w2 = h // 2, w // 2
    x = x[:, :, :h2 * 2, :w2 * 2].reshape(b, c, h2, 2, w2, 2)
    return x.max(axis=(3, 5)) if kind == "max" else x.mean(axis=(3, 5))


BACKENDS = ("einsum", "pallas_staged", "pallas_fused")


def _epilogue_spatial(x: Array, lp) -> Array:
    """Bias + ReLU for the backends that don't fuse it into the kernel."""
    if lp.epilogue.bias:
        x = x + lp.bias[0][None, :, None, None]
    if lp.epilogue.relu:
        x = jax.nn.relu(x)
    return x


def forward_spectral(params: dict, plan, x: Array, *,
                     backend: str = "einsum",
                     interpret: bool | None = None,
                     guards: res.NumericGuards | None = None) -> Array:
    """Inference by executing a precompiled ``core.plan.NetworkPlan``.

    Args:
      params: the weights ``init`` produced (the conv stack reads only
        the plan's baked operands, but the FC head reads ``params``).
      plan: a ``core.plan.NetworkPlan`` built ONCE by
        ``build_network_plan`` for this config and batch size.
      x: [B, C, H, W] f32 input batch; must match the plan's layer
        geometry, and for the fused backend on hardware the plan's
        tuned batch (RMW-flow safety — see the error message).
      backend: conv-stack implementation, one of ``BACKENDS``:
        'einsum'        pure-jnp oracle (sparse-aware masked einsum);
        'pallas_staged' 3 pallas_calls/layer: fft8 -> hadamard ->
                        ifft8, spectral intermediates round-tripping
                        through HBM;
        'pallas_fused'  ONE pallas_call/layer executing the plan's
                        precompiled operands with bias+ReLU fused into
                        the kernel flush.  Each layer runs the Hadamard
                        datapath its plan chose (``LayerPlan.hadamard``):
                        'dense'/'bin' stream (compacted) kernel planes
                        through the Karatsuba GEMM, 'scheduled' executes
                        the layer's Alg-2 INDEX/VALUE tables element-
                        granularly (``execute_layer_plan`` dispatches).
      interpret: force Pallas interpret mode (None = auto: interpret
        everywhere except real TPU).
      guards: optional ``core.resilience.NumericGuards`` enabling the
        opt-in per-layer runtime checks (NaN/Inf scan, sampled parity
        vs the einsum oracle) on the Pallas backends, with policy
        'raise' | 'demote' | 'warn'.  Every trip is appended to
        ``guards.events``.

    Under the 'pallas_fused' backend each layer runs the execution path
    its plan records (``LayerPlan.backend`` — 'fused' as built, or
    'staged'/'einsum' after ``resilience.harden_network_plan`` demoted
    it), and any unexpected per-layer failure is re-raised as a
    structured ``resilience.KernelLoweringError`` naming the layer and
    its modes — never a raw Pallas traceback.

    Returns [B, n_classes] logits.  Everything layer-specific was
    derived at plan-build time; nothing (geometry, schedules, pruning,
    table compilation, autotune) is rebuilt here, so repeated calls go
    straight to the jit cache.

    Each node's dispatch runs inside a ``forward.node`` profiler span
    and the head inside ``forward.fc_head`` (``core.spans``).
    """
    if backend not in BACKENDS:
        raise ValueError(f"backend must be one of {BACKENDS}")
    if backend == "pallas_fused" and x.shape[0] != plan.batch:
        from repro.kernels.ops import default_interpret
        on_hw = not (default_interpret() if interpret is None
                     else interpret)
        rmw = [lp.layer.name for lp in plan.layers
               if lp.tuning.flow != "output_stationary"]
        if on_hw and rmw:
            # the RMW flows' hardware-safety (single p/n block) was
            # established for plan.batch; a different batch changes P
            # and would fail deep inside the kernel with a less useful
            # error
            raise ValueError(
                f"plan was autotuned for batch {plan.batch} but got "
                f"batch {x.shape[0]}; RMW-flow layers {rmw} are only "
                f"hardware-safe at the tuned batch — rebuild with "
                f"build_network_plan(..., batch={x.shape[0]})")
    from repro.core.plan import graph_sink
    graph = plan.execution_graph
    out_id = graph_sink(graph)
    # Reference counts so large intermediate activations are freed as
    # soon as their last consumer (main or shortcut edge) has run.
    refs: dict[str, int] = {out_id: 1}
    for node in graph:
        for src in (node.inputs[0], node.residual_from):
            if src is not None:
                refs[src] = refs.get(src, 0) + 1
    acts: dict[str, Array] = {"input": x}
    for node in graph:
        src = acts[node.inputs[0]]
        if node.kind == "pool":
            with spans.span(spans.FORWARD_NODE, node=node.id, kind="pool"):
                y = _pool(src, node.pool)
        else:
            lp = plan.layers[node.layer_index]
            if src.shape[1:] != (lp.layer.c_in, lp.layer.h_in,
                                 lp.layer.w_in):
                raise ValueError(
                    f"plan/input mismatch at {node.id}: plan expects "
                    f"[B, {lp.layer.c_in}, {lp.layer.h_in}, "
                    f"{lp.layer.w_in}], got {src.shape}")
            sc = (acts[node.residual_from]
                  if node.residual_from is not None else None)
            with spans.span(
                    spans.FORWARD_NODE, node=node.id, kind="conv",
                    hadamard=lp.hadamard, flow=lp.tuning.flow,
                    input_mode=lp.input_mode,
                    residual=getattr(lp.epilogue, "residual", None),
                    backend=(getattr(lp, "backend", "fused")
                             if backend == "pallas_fused" else backend),
                    predicted_us=lp.tuning.predicted_s * 1e6):
                y = _conv_node(src, lp, node, sc, backend, interpret,
                               guards)
        acts[node.id] = y
        for s in (node.inputs[0], node.residual_from):
            if s is not None:
                refs[s] -= 1
                if refs[s] == 0:
                    acts.pop(s, None)
    with spans.span(spans.FORWARD_FC_HEAD):
        return fc_head(params, acts[out_id])


def fc_head(params: dict, x: Array) -> Array:
    """The classifier on the flattened features: three dense layers, at
    HIGHEST precision (a TPU rounds f32 matmul inputs to bf16 by
    default, which the logits' 1e-4 parity bound does not allow)."""
    hp = jax.lax.Precision.HIGHEST
    x = x.reshape(x.shape[0], -1)
    x = jax.nn.relu(jnp.matmul(x, params["fc1"], precision=hp))
    x = jax.nn.relu(jnp.matmul(x, params["fc2"], precision=hp))
    return jnp.matmul(x, params["fc3"], precision=hp)


def _conv_node(x: Array, lp, node, sc: Array | None, backend: str,
               interpret: bool | None,
               guards: res.NumericGuards | None) -> Array:
    """Execute one conv DAG node under the chosen network backend.

    Epilogue ordering is uniform across every path: bias -> stride
    subsample -> (+shortcut) -> ReLU.  (Bias and ReLU are elementwise,
    so applying them before or after the ``[::stride]`` subsample is
    numerically identical; the shortcut always matches the POST-stride
    output shape.)  Residual-FUSED nodes on the fused backend do bias +
    shortcut + ReLU inside the kernel flush; every other combination —
    the 'add' rung, strided nodes, staged/einsum paths — applies the
    add as a plain XLA op with the ReLU deferred until after it.
    """
    stride = getattr(lp.layer, "stride", 1)
    residual = getattr(lp.epilogue, "residual", None)
    if backend == "einsum":
        y = spec.spectral_conv2d_pretransformed(x, lp.kernels, lp.geo)
        if lp.epilogue.bias:
            y = y + lp.bias[0][None, :, None, None]
        y = y[:, :, ::stride, ::stride]
        if sc is not None:
            y = y + sc
        if node.relu:
            y = jax.nn.relu(y)
        return y
    if backend == "pallas_staged":
        from repro.kernels import ops
        y = ops.spectral_conv2d_pallas(x, lp.kernels.values, lp.geo,
                                       interpret=interpret)
        if sc is None:
            y = _epilogue_spatial(y, lp)
            if guards is not None:
                y = res.apply_guards(x, y, lp, guards)
            return y[:, :, ::stride, ::stride]
        # Residual node: ReLU defers until after the add, so guard the
        # bias-only output (parity oracle with relu disabled), then
        # subsample -> add -> ReLU.
        if lp.epilogue.bias:
            y = y + lp.bias[0][None, :, None, None]
        if guards is not None:
            lp_nr = dataclasses.replace(
                lp, epilogue=dataclasses.replace(lp.epilogue,
                                                 relu=False))
            y = res.apply_guards(x, y, lp_nr, guards)
        y = y[:, :, ::stride, ::stride] + sc
        return jax.nn.relu(y) if node.relu else y
    # pallas_fused: the plan's per-layer backend decides the path.
    fuse_in_kernel = (residual == "fused" and sc is not None
                      and getattr(lp, "backend", "fused") == "fused")
    try:
        y = res.execute_planned_layer(
            x, lp, interpret=interpret,
            shortcut=sc if fuse_in_kernel else None)
    except res.ResilienceError:
        raise
    except Exception as e:
        raise res.KernelLoweringError(
            f"layer {lp.layer.name} failed under backend="
            f"{getattr(lp, 'backend', 'fused')!r} (flow="
            f"{lp.tuning.flow}, hadamard={lp.hadamard}, "
            f"input_mode={lp.input_mode}): {e}",
            layer=lp.layer.name, site="forward") from e
    if guards is not None:
        y = res.apply_guards(x, y, lp, guards,
                             shortcut=sc if fuse_in_kernel else None)
    if not fuse_in_kernel:
        y = y[:, :, ::stride, ::stride]
        if sc is not None:
            y = y + sc
            if node.relu:
                y = jax.nn.relu(y)
    return y


def forward_spatial(params: dict, cfg: SpectralCNNConfig, x: Array) -> Array:
    """Dense spatial-domain oracle of the same network.

    Walks the SAME DAG the spectral executors walk (explicit
    ``cfg.graph`` or the synthesized linear chain) entirely in the
    spatial domain — stride-2 convs, max/avg pool nodes and residual
    adds included, with the canonical epilogue ordering bias -> stride
    -> (+shortcut) -> ReLU.  This is the reference every backend,
    degradation rung and shard strategy is diffed against (ISSUE 10's
    oracle-diff harness).
    """
    from repro.core.plan import graph_sink
    order = _config_graph(cfg)
    convs = {layer.name: (layer, conv)
             for layer, conv in zip(cfg.layers, params["convs"])}
    acts: dict[str, Array] = {"input": x}
    for s in order:
        src = acts[s.inputs[0]]
        if s.kind == "pool":
            y = _pool(src, s.pool)
        else:
            layer, conv = convs[s.id]
            stride = getattr(layer, "stride", 1)
            y = spec.spatial_conv2d(src, conv["w"], pad=layer.pad,
                                    stride=stride)
            y = y + conv["b"][None, :, None, None]
            if s.residual_from is not None:
                y = y + acts[s.residual_from]
            if s.relu:
                y = jax.nn.relu(y)
        acts[s.id] = y
    x = acts[graph_sink(order)]
    return fc_head(params, x)
