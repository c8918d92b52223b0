"""Ahead-of-time compiles of the Pallas kernels for a TPU v5e, on the CPU.

The TPU compiler is installed here and compiles for a chip that is
described, not attached (``jax.experimental.topologies``).  Each test
lowers one kernel at a real width, with the blocks Alg 1
(``autotune.autotune_layer``) returns, and asserts that Mosaic compiled
it (``tpu_custom_call`` in the compiled HLO).  This catches what
interpret mode cannot: blocks off the (8, 128) tiling, unsupported
in-kernel reshapes, VMEM over the scoped limit.  Nothing runs.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library, and every test worker
imports this file.
"""

import math

import jax
import jax.numpy as jnp
import pytest

from repro.configs import resnet18_spectral
from repro.core import autotune as at
from repro.core import dataflow as df
from repro.core import spectral as spec

FFT = 8
ALPHA = 4.0
VGG = {l.name: l for l in df.VGG16_LAYERS}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:           # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile here is written to the persistent cache but can never
    # be read back without a chip; keep the cache out of it.
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield topo
    jax.config.update("jax_enable_compilation_cache", cache_was)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def four_chips(topo):
    import numpy as np
    from jax.sharding import Mesh
    return Mesh(np.asarray(topo.devices), ("shard",))


def _shape(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def _compile_fused(sharding, layer, tn, batch, *, residual=False):
    """Compile ``_fused_conv`` for one layer at its tuned blocks.  Alg-2
    tables are given as shapes from the cost model's table-size
    estimate (T = max(nnz, nnz / mu) cycles)."""
    from repro.kernels.fused_spectral_conv import _fused_conv
    S = lambda *a: _shape(sharding, *a)
    geo = spec.make_geometry(layer.h_in, layer.w_in, layer.ksize, FFT,
                             layer.pad)
    n, m, fa, s2 = layer.c_out, layer.c_in, FFT * FFT, geo.tile ** 2
    if tn.hadamard == "scheduled":
        npe = min(tn.block_n, n)
        bm = min(tn.block_m, m)
        tabs = (-(-n // npe), -(-m // bm) * bm)
        nnz = round(fa / ALPHA)
        t_cyc = max(nnz, math.ceil(nnz / df.SCHEDULE_MU))
        w_ops = (S(tabs + (t_cyc, df.SCHEDULE_R), jnp.int32),
                 S(tabs + (t_cyc, npe), jnp.int32),
                 S(tabs + (t_cyc, npe)), S(tabs + (t_cyc, npe)))
        block_n = npe
    else:
        w_ops = (S((fa, n, m)), S((fa, n, m)))
        block_n = tn.block_n
    args = [S((batch, m, layer.h_in, layer.w_in)), w_ops,
            S((fa, fa)), S((fa, fa)), S((s2, fa)), S((s2, fa)), S((1, n))]
    if residual:
        args.append(S((batch, n) + layer.out_hw))

    def fn(x, w, dfr, dfi, dvr, dvi, bias, *sc):
        return _fused_conv(x, w, dfr, dfi, dvr, dvi, bias, *sc, geo=geo,
                           input_mode=tn.input_mode or "windowed",
                           flow=tn.flow, block_n=block_n,
                           block_m=tn.block_m, block_p=tn.block_p,
                           relu=True, interpret=False)
    return _compile(fn, *args)


@pytest.mark.parametrize("batch", [1, 8])
@pytest.mark.parametrize("name,hadamard", [("conv1_1", "scheduled"),
                                           ("conv3_1", "dense"),
                                           ("conv5_1", "scheduled")])
def test_fused_halo_compiles(one_chip, name, hadamard, batch):
    tn = at.autotune_layer(VGG[name], FFT, ALPHA, batch=batch,
                           hadamard_modes=[hadamard], input_modes=["halo"])
    assert (tn.hadamard, tn.input_mode) == (hadamard, "halo")
    _compile_fused(one_chip, VGG[name], tn, batch)


def test_fused_windowed_compiles(one_chip):
    """conv3_1 as Alg 1 ranks every mode: the windowed rung."""
    tn = at.autotune_layer(VGG["conv3_1"], FFT, ALPHA, batch=1,
                           hadamard_modes=["dense", "scheduled"],
                           input_modes=df.INPUT_MODES)
    tn = at.autotune_layer(VGG["conv3_1"], FFT, ALPHA, batch=1,
                           hadamard_modes=[tn.hadamard],
                           input_modes=["windowed"])
    _compile_fused(one_chip, VGG["conv3_1"], tn, 1)


def test_fused_plane_node_compiles(one_chip):
    """A 512->512 node as Alg 1 tunes it at batch 1: the plane datapath
    (the table expansion costs more than the planes' bytes), at its
    tuned flow and blocks."""
    layer = VGG["conv4_2"]
    tn = at.autotune_layer(layer, FFT, ALPHA, batch=1,
                           hadamard_modes=["dense", "scheduled"],
                           input_modes=df.INPUT_MODES)
    assert tn.hadamard == "dense"
    _compile_fused(one_chip, layer, tn, 1)


def test_fused_residual_compiles(one_chip):
    """A ResNet-18 residual-fused node: the shortcut is one more kernel
    operand, added on the epilogue flush."""
    cfg = resnet18_spectral.CONFIG
    layers = {l.name: l for l in cfg.layers}
    node = next(s for s in cfg.graph
                if s.residual_from is not None
                and layers[s.id].stride == 1)
    layer = layers[node.id]
    tn = at.autotune_layer(layer, FFT, ALPHA, batch=1,
                           hadamard_modes=["dense", "scheduled"],
                           input_modes=df.INPUT_MODES, residual="vmem")
    _compile_fused(one_chip, layer, tn, 1, residual=True)


def test_staged_fft8_compiles(one_chip):
    from repro.kernels import fft8
    # conv3_1's windows: 128 channels x 100 tiles of 8x8
    x = _shape(one_chip, (128 * 100, FFT, FFT))
    _compile(lambda x: fft8.fft2_tiles(x, fft_size=FFT, interpret=False), x)


def test_staged_spectral_hadamard_compiles(one_chip):
    from repro.kernels.spectral_hadamard import spectral_hadamard
    fa = FFT * FFT
    w = _shape(one_chip, (fa, 256, 128))        # conv3_1: N=256, M=128
    x = _shape(one_chip, (fa, 128, 100))        # P = 100 tiles
    _compile(lambda wr, wi, xr, xi: spectral_hadamard(
        wr, wi, xr, xi, interpret=False), w, w, x, x)


def test_staged_sparse_hadamard_compiles(one_chip):
    from repro.kernels.sparse_hadamard import scheduled_sparse_hadamard
    S = lambda *a: _shape(one_chip, *a)
    m, t, r, npe, fa = 128, 19, df.SCHEDULE_R, 64, FFT * FFT
    args = (S((m, t, r), jnp.int32), S((m, t, npe), jnp.int32),
            S((m, t, npe)), S((m, t, npe)), S((m, t, npe)),
            S((m, t, npe), jnp.int32), S((m, fa, 100)), S((m, fa, 100)))
    _compile(lambda *a: scheduled_sparse_hadamard(*a, interpret=False),
             *args)


@pytest.fixture(scope="module")
def smoke_net():
    from repro.configs import vgg16_spectral
    from repro.core.plan import build_network_plan
    from repro.models import cnn
    cfg = vgg16_spectral.SMOKE
    params = cnn.init(jax.random.PRNGKey(0), cfg)
    return cfg, params, build_network_plan(params, cfg, batch=1,
                                           schedule=False)


@pytest.mark.parametrize("strategies", [("channel",), ("spatial",), None],
                         ids=["channel", "spatial", "auto"])
def test_sharded_forward_compiles(four_chips, smoke_net, strategies):
    """The whole sharded forward as one program over a 2x2 v5e mesh:
    every kernel, replicated layers included, runs inside a shard_map
    (Mosaic kernels cannot be partitioned automatically)."""
    from jax.sharding import NamedSharding, PartitionSpec
    from repro.core.plan import build_sharded_network_plan
    from repro.distributed.executor import forward_spectral_sharded
    cfg, params, base = smoke_net
    splan = build_sharded_network_plan(params, cfg, n_shards=4, batch=1,
                                       strategies=strategies, base=base,
                                       schedule=False)
    if strategies is not None:
        assert strategies[0] in splan.strategies.values()
    assert "replicate" in splan.strategies.values()
    l0 = cfg.layers[0]
    x = jax.ShapeDtypeStruct((1, l0.c_in, l0.h_in, l0.w_in), jnp.float32,
                             sharding=NamedSharding(four_chips,
                                                    PartitionSpec()))
    text = _compile(lambda x: forward_spectral_sharded(
        params, splan, x, mesh=four_chips, interpret=False), x)
    assert text.count("tpu_custom_call") >= len(cfg.layers)
