"""Fused single-pallas_call spectral conv == einsum oracle == spatial conv.

Covers the tentpole kernel (kernels/fused_spectral_conv.py): FFT ->
Hadamard -> IFFT in one kernel, across fft sizes, non-divisible
geometries (tile-padding edge), all three residency flows, pruned
kernels, and the autotuner that configures it.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import autotune
from repro.core import dataflow as df
from repro.core import sparse as sp
from repro.core import spectral as spec
from repro.kernels.fused_spectral_conv import FLOWS, fused_spectral_conv2d


def _conv_case(h, w, k, K, cin, cout, batch=2, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((batch, cin, h, w)), jnp.float32)
    wk = jnp.asarray(rng.standard_normal((cout, cin, k, k)), jnp.float32)
    geo = spec.make_geometry(h, w, k, K)
    return x, wk, geo


class TestFusedVsOracles:
    @pytest.mark.parametrize("flow", FLOWS)
    @pytest.mark.parametrize(
        "h,w,k,K,cin,cout,blocks",
        [
            (12, 12, 3, 8, 3, 5, (4, 2, 16)),     # blocks divide nothing
            (14, 14, 3, 8, 4, 4, (4, 4, 9)),      # VGG conv5 spatial size
            (11, 13, 3, 8, 2, 3, (8, 8, 8)),      # non-divisible, rect
            (16, 16, 5, 8, 2, 2, (2, 2, 32)),     # k=5
            (24, 24, 3, 16, 2, 2, (2, 2, 4)),     # K=16
            (6, 6, 3, 8, 1, 1, (8, 8, 8)),        # single tile
        ],
    )
    def test_vs_spatial(self, flow, h, w, k, K, cin, cout, blocks):
        x, wk, geo = _conv_case(h, w, k, K, cin, cout)
        bn, bm, bp = blocks
        y = fused_spectral_conv2d(x, spec.spectral_kernel(wk, K), geo,
                                  flow=flow, block_n=bn, block_m=bm,
                                  block_p=bp)
        y_spatial = spec.spatial_conv2d(x, wk)
        y_spectral = spec.spectral_conv2d(x, wk, fft_size=K)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_spatial),
                                   atol=2e-4, rtol=2e-4)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_spectral),
                                   atol=2e-4, rtol=2e-4)

    @pytest.mark.parametrize("flow", FLOWS)
    @pytest.mark.parametrize("alpha", [2.0, 4.0])
    def test_pruned_vs_einsum_oracle(self, flow, alpha):
        """Pruned (alpha > 1) kernels: fused == sparse-aware oracle."""
        x, wk, geo = _conv_case(13, 12, 3, 8, 4, 6, seed=3)
        sk = sp.prune_magnitude(spec.spectral_kernel(wk, 8), alpha)
        y = fused_spectral_conv2d(x, sk, geo, flow=flow,
                                  block_n=4, block_m=4, block_p=16)
        y_ref = spec.spectral_conv2d_pretransformed(x, sk, geo)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   atol=2e-4, rtol=2e-4)

    def test_flows_agree(self):
        x, wk, geo = _conv_case(18, 18, 3, 8, 3, 4, seed=5)
        wf = spec.spectral_kernel(wk, 8)
        outs = [fused_spectral_conv2d(x, wf, geo, flow=fl, block_n=2,
                                      block_m=2, block_p=8)
                for fl in FLOWS]
        for y in outs[1:]:
            np.testing.assert_allclose(np.asarray(y), np.asarray(outs[0]),
                                       atol=1e-4, rtol=1e-4)

    def test_oversized_blocks_clamped(self):
        x, wk, geo = _conv_case(10, 10, 3, 8, 2, 3, batch=1)
        y = fused_spectral_conv2d(x, spec.spectral_kernel(wk, 8), geo,
                                  block_n=512, block_m=512, block_p=512)
        np.testing.assert_allclose(np.asarray(y),
                                   np.asarray(spec.spatial_conv2d(x, wk)),
                                   atol=2e-4, rtol=2e-4)


class TestSparseOracle:
    """The einsum oracle's masked (active-bin) path (satellite fix)."""

    def test_sparse_equals_dense_values(self):
        rng = np.random.default_rng(7)
        x = jnp.asarray(rng.standard_normal((1, 4, 12, 12)), jnp.float32)
        wk = jnp.asarray(rng.standard_normal((6, 4, 3, 3)), jnp.float32)
        geo = spec.make_geometry(12, 12, 3, 8)
        sk = sp.prune_magnitude(spec.spectral_kernel(wk, 8), 8.0)
        # the high-alpha magnitude pattern leaves whole bins empty, so
        # the gather path is actually exercised
        active = np.asarray(sk.mask).any(axis=(0, 1)).reshape(-1).sum()
        assert active < 64
        y = spec.spectral_conv2d_pretransformed(x, sk, geo)
        y_ref = spec.spectral_conv2d_pretransformed(x, sk.values, geo)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   atol=1e-5)


class TestAutotune:
    def test_plan_fits_budget(self):
        plan = autotune.autotune_network(
            df.VGG16_LAYERS, 8, 4.0, hadamard_modes=("dense", "scheduled"),
            input_modes=df.INPUT_MODES)
        assert set(plan) == {l.name for l in df.VGG16_LAYERS}
        for tn in plan.values():
            assert tn.flow in FLOWS
            assert tn.vmem_bytes <= df.TPU_VMEM_BYTES

    def test_plan_covers_layer_dims(self):
        """Manual-DMA accumulators (PR 8) lift the consecutive-revisit
        restriction, so RMW flows may split p/n freely; the invariant
        that remains is coverage — the block grid must tile the full
        layer dims (validated by core.resilience 'dma/tile-bounds')."""
        layers = {l.name: l for l in df.VGG16_LAYERS}
        plan = autotune.autotune_network(df.VGG16_LAYERS, 8, 4.0)
        for name, tn in plan.items():
            layer = layers[name]
            assert 1 <= tn.block_n and 1 <= tn.block_m and 1 <= tn.block_p
            gn = -(-layer.c_out // tn.block_n)
            assert gn * tn.block_n >= layer.c_out

    def test_split_rmw_runs_without_guard(self):
        """block_p < tiles on weight_stationary — rejected by the old
        hardware guard — now runs and matches the full-p result."""
        x, wk, geo = _conv_case(24, 24, 3, 8, 2, 3, batch=1)
        wf = spec.spectral_kernel(wk, 8)
        y = fused_spectral_conv2d(x, wf, geo, flow="weight_stationary",
                                  block_p=4)
        y_ref = fused_spectral_conv2d(x, wf, geo,
                                      flow="weight_stationary")
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   atol=1e-5, rtol=1e-5)

    def test_cost_model_consistency(self):
        """Fused kernel's HBM bytes <= the staged pipeline's
        output-stationary prediction — the whole point of fusing.  Both
        models pad the tile (lane) axis to whole 128-lane blocks."""
        for layer in df.VGG16_LAYERS:
            fused = df.tpu_fused_flow_cost(layer, 8, 4.0, 64, 128, 64,
                                           "output_stationary")
            staged = df.tpu_flow_cost(layer, 8, 4.0, 64, 128, 64,
                                      "output_stationary")
            assert fused["hbm_bytes"] <= staged["hbm_bytes"], layer.name

    def test_measured_autotune_smoke(self):
        layer = df.ConvLayer("tiny", 4, 8, 12, 12)
        tn = autotune.autotune_layer(
            layer, 8, 4.0,
            blocks=(4, 8),
            measure_fn=autotune._make_measure_fn(layer, 8, 4.0, 1, True),
            measure_top_k=2)
        assert tn.measured_s is not None and tn.measured_s > 0

    def test_tuned_plan_runs_through_model(self):
        from repro.configs import vgg16_spectral
        from repro.core.plan import build_network_plan
        from repro.models import cnn
        cfg = vgg16_spectral.SMOKE
        params = cnn.init(jax.random.PRNGKey(0), cfg)
        plan = build_network_plan(params, cfg, batch=1)
        x = jax.random.normal(jax.random.PRNGKey(1),
                              (1, 3, cfg.image_size, cfg.image_size))
        ref = cnn.forward_spectral(params, plan, x)
        out = cnn.forward_spectral(params, plan, x, backend="pallas_fused")
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-3, rtol=1e-3)
