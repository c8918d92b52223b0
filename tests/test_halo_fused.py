"""In-kernel halo gather (input_mode='halo') — the PR-5 tentpole.

The fused kernel's halo input path reads the RAW NCHW activation
through overlapping (element-offset) input blocks and gathers the
overlap-save windows in VMEM with one-hot matmuls, eliminating the
host-materialized [B, M, T, K, K] window tensor.  Covered here:

  * halo == windowed parity per flow x Hadamard mode (BIT-exact: the
    gather is a 0/1 matmul selecting one value per output), and <= 1e-5
    vs the einsum oracle with the fused bias+ReLU epilogue;
  * the halo-block geometry property: the clamped blocks + gather
    matrices reproduce ``extract_tiles_overlapping`` for every
    (H, W, k, K, block_p) the plan can emit (hypothesis);
  * the repriced cost model (``tpu_fused_flow_cost(input_mode=...)``):
    halo input bytes < windowed on every VGG16 layer and flow;
  * the autotune input-mode axis (halo + weight_stationary is legal at
    any batch since the PR-8 manual-DMA accumulators);
  * plan-level integration: ``build_network_plan(input_mode=...)``
    threads the mode into ``LayerPlan`` and ``execute_layer_plan``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs import vgg16_spectral
from repro.core import autotune, dataflow as df
from repro.core import sparse as sp
from repro.core import spectral as spec
from repro.core.plan import build_network_plan
from repro.kernels.fused_spectral_conv import (
    FLOWS, fused_spectral_conv2d, fused_spectral_conv2d_scheduled)
from repro.models import cnn

KEY = jax.random.PRNGKey(0)


def _case(h=13, w=12, cin=4, cout=6, k=3, K=8, batch=2, seed=3):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((batch, cin, h, w)), jnp.float32)
    wk = jnp.asarray(rng.standard_normal((cout, cin, k, k)), jnp.float32)
    b = jnp.asarray(0.1 * rng.standard_normal(cout), jnp.float32)
    geo = spec.make_geometry(h, w, k, K)
    return x, wk, b, geo


class TestHaloParity:
    """halo == windowed (exact) == oracle (<= 1e-5), flows x modes."""

    @pytest.mark.parametrize("flow", FLOWS)
    @pytest.mark.parametrize("mode", df.HADAMARD_MODES)
    def test_flow_mode_matrix(self, flow, mode):
        x, wk, b, geo = _case()
        sk = sp.prune_magnitude(spec.spectral_kernel(wk, 8), 4.0)
        w_f = sk.values if mode == "dense" else sk
        run = {}
        for imode in df.INPUT_MODES:
            if mode == "scheduled":
                run[imode] = fused_spectral_conv2d_scheduled(
                    x, sk, geo, n_par=4, r=6, flow=flow, block_m=2,
                    block_p=8, bias=b, relu=True, input_mode=imode)
            else:
                run[imode] = fused_spectral_conv2d(
                    x, w_f, geo, flow=flow, block_n=4, block_m=2,
                    block_p=5, bias=b, relu=True, input_mode=imode)
        # one-hot gather => the halo path is numerically identical
        np.testing.assert_array_equal(np.asarray(run["halo"]),
                                      np.asarray(run["windowed"]))
        y_ref = jax.nn.relu(
            spec.spectral_conv2d_pretransformed(x, sk, geo)
            + b[None, :, None, None])
        err = float(jnp.abs(run["halo"] - y_ref).max())
        assert err <= 1e-5, (flow, mode, err)

    def test_dense_vs_spatial(self):
        """Un-pruned halo path equals the spatial conv oracle."""
        x, wk, b, geo = _case(h=18, w=17, cin=3, cout=5)
        y = fused_spectral_conv2d(x, spec.spectral_kernel(wk, 8), geo,
                                  block_n=4, block_m=2, block_p=7,
                                  input_mode="halo")
        np.testing.assert_allclose(np.asarray(y),
                                   np.asarray(spec.spatial_conv2d(x, wk)),
                                   atol=2e-4, rtol=2e-4)

    @pytest.mark.parametrize("block_p", [1, 3, 9, 128])
    def test_block_split_invariance(self, block_p):
        """Any block_p split of the tile grid gives the same output."""
        x, wk, b, geo = _case(h=14, w=14)
        wf = spec.spectral_kernel(wk, 8)
        y = fused_spectral_conv2d(x, wf, geo, block_n=4, block_m=2,
                                  block_p=block_p, input_mode="halo")
        y_ref = fused_spectral_conv2d(x, wf, geo, block_n=4, block_m=2,
                                      block_p=block_p)
        np.testing.assert_array_equal(np.asarray(y), np.asarray(y_ref))

    def test_ws_halo_batch2_runs_and_matches(self):
        """halo weight_stationary at batch > 1 — hardware-illegal before
        the manual-DMA accumulators (PR 8) — now runs and matches the
        spatial reference."""
        x, wk, b, geo = _case(h=12, w=12, batch=2)
        y = fused_spectral_conv2d(x, spec.spectral_kernel(wk, 8), geo,
                                  flow="weight_stationary", block_p=512,
                                  input_mode="halo")
        np.testing.assert_allclose(np.asarray(y),
                                   np.asarray(spec.spatial_conv2d(x, wk)),
                                   atol=2e-4, rtol=2e-4)

    def test_bad_input_mode_raises(self):
        x, wk, b, geo = _case()
        with pytest.raises(ValueError, match="input_mode"):
            fused_spectral_conv2d(x, spec.spectral_kernel(wk, 8), geo,
                                  input_mode="nope")


class TestHaloGeometry:
    """The clamped halo blocks + one-hot gather tile every geometry."""

    @settings(max_examples=40, deadline=None)
    @given(h=st.integers(2, 34), w=st.integers(2, 34),
           k=st.sampled_from([3, 5]), K=st.sampled_from([8, 16]),
           block_p=st.integers(1, 64))
    def test_reference_equals_windowed_extraction(self, h, w, k, K,
                                                  block_p):
        geo = spec.make_geometry(h, w, k, K)
        hg = spec.halo_block_geometry(geo, block_p)
        rng = np.random.default_rng(h * 100 + w)
        x = jnp.asarray(rng.standard_normal((1, 2, h, w)), jnp.float32)
        ref = spec.extract_tiles_overlapping(x, geo)
        got = spec.halo_window_reference(x, geo, hg)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))

    def test_blocks_cover_tile_grid(self):
        for (h, w, k, K, bp) in [(224, 224, 3, 8, 128), (14, 14, 3, 8, 9),
                                 (11, 7, 5, 8, 3), (2, 2, 3, 8, 16)]:
            geo = spec.make_geometry(h, w, k, K)
            hg = spec.halo_block_geometry(geo, bp)
            assert hg.nbh * hg.bth >= geo.n_tiles_h
            assert hg.nbw * hg.btw >= geo.n_tiles_w
            assert hg.block_tiles <= max(1, bp)
            assert hg.rh <= geo.h_in and hg.rw <= geo.w_in
            sh, sw = spec.halo_block_starts(geo, hg)
            assert (sh >= 0).all() and (sh + hg.rh <= geo.h_in).all()
            assert (sw >= 0).all() and (sw + hg.rw <= geo.w_in).all()


class TestRepricedCostModel:
    def test_halo_input_bytes_below_windowed_all_layers(self):
        """Acceptance: raw-plus-halo input words beat the materialized
        window stream on every VGG16 layer and flow."""
        lower = set()
        for layer in df.VGG16_LAYERS:
            for flow in df.FLOWS:
                w = df.tpu_fused_flow_cost(layer, 8, 4.0, 64, 128, 64,
                                           flow, input_mode="windowed")
                h = df.tpu_fused_flow_cost(layer, 8, 4.0, 64, 128, 64,
                                           flow, input_mode="halo")
                assert (h["input_hbm_bytes"]
                        < w["input_hbm_bytes"]), (layer.name, flow)
                # the rest of halo's traffic is its tile stream, padded
                # to a lane tile per tile row: never below windowed's
                assert (h["hbm_bytes"] - h["input_hbm_bytes"]
                        >= w["hbm_bytes"] - w["input_hbm_bytes"]), (
                    layer.name, flow)
                if h["hbm_bytes"] < w["hbm_bytes"]:
                    lower.add((layer.name, flow))
        # in total, halo moves fewer bytes only where the lane padding
        # costs less than the window stream saves: conv1_2 (224 wide)
        # and the kernel-bound conv4/conv5 under output-stationary
        assert lower == ({("conv1_2", f) for f in df.FLOWS}
                         | {(f"conv{i}_{j}", "output_stationary")
                            for i in (4, 5) for j in (1, 2, 3)})

    def test_input_share_accounted(self):
        """input + kernel shares never exceed the total."""
        layer = df.VGG16_LAYERS[5]
        for imode in df.INPUT_MODES:
            c = df.tpu_fused_flow_cost(layer, 8, 4.0, 64, 128, 64,
                                       "output_stationary",
                                       input_mode=imode)
            assert c["input_mode"] == imode
            assert (c["input_hbm_bytes"] + c["kernel_hbm_bytes"]
                    <= c["hbm_bytes"])

    def test_legacy_default_is_windowed(self):
        layer = df.VGG16_LAYERS[3]
        legacy = df.tpu_fused_flow_cost(layer, 8, 4.0, 64, 128, 64,
                                        "output_stationary")
        windowed = df.tpu_fused_flow_cost(layer, 8, 4.0, 64, 128, 64,
                                          "output_stationary",
                                          input_mode="windowed")
        assert legacy == windowed

    def test_bad_input_mode_raises(self):
        with pytest.raises(ValueError, match="input_mode"):
            df.tpu_fused_flow_cost(df.VGG16_LAYERS[0], 8, 4.0, 64, 128,
                                   64, "output_stationary",
                                   input_mode="nope")


class TestInputModeAutotune:
    def test_axis_picks_halo_on_vgg16(self):
        """With both modes offered, Alg 1 ranks halo against windowed
        per layer.  Priced on the TPU's (8, 128) tiling, halo pads each
        tile row to a 128-lane tile (38 of 128 lanes used at 224x224, 5
        at 28x28), and windowed wins every VGG16 layer at batch 1 and
        8.  Before the padding was priced, halo won all 13."""
        for batch in (1, 8):
            picked = {layer.name: autotune.autotune_layer(
                layer, 8, 4.0, batch=batch,
                input_modes=df.INPUT_MODES).input_mode
                for layer in df.VGG16_LAYERS}
            assert picked == {layer.name: "windowed"
                              for layer in df.VGG16_LAYERS}, batch

    def test_ws_halo_eligible_at_batch_gt_1(self):
        """Manual-DMA accumulators (PR 8) lift the batch-1 limit on halo
        weight-stationary: the tuner may now pick it at batch 2, and
        whatever it picks must validate in a built plan (hw_safe is a
        no-op)."""
        layer = df.ConvLayer("tiny", 4, 8, 12, 12)
        tn = autotune.autotune_layer(
            layer, 8, 4.0, batch=2, flows=("weight_stationary",),
            input_modes=df.INPUT_MODES)
        assert tn.input_mode in df.INPUT_MODES
        # halo is no longer excluded from the candidate set
        cands = [
            (f, bn, bm, bp)
            for f, bn, bm, bp in autotune._layer_candidates(
                layer, 8, 2, autotune.BLOCK_CANDIDATES, True)]
        assert any(f == "weight_stationary" for f, *_ in cands)

    def test_legacy_mode_is_none(self):
        tn = autotune.autotune_layer(df.VGG16_LAYERS[3], 8, 4.0)
        assert tn.input_mode is None


class TestPlanIntegration:
    def test_auto_plan_records_mode_and_matches_oracle(self):
        cfg = vgg16_spectral.SMOKE
        params = cnn.init(KEY, cfg)
        plan = build_network_plan(params, cfg, batch=1)
        assert all(lp.input_mode in df.INPUT_MODES for lp in plan.layers)
        assert any(lp.input_mode == "halo" for lp in plan.layers)
        for lp in plan.layers:
            assert lp.stats()["input_mode"] == lp.input_mode
        x = jax.random.normal(jax.random.PRNGKey(1),
                              (1, 3, cfg.image_size, cfg.image_size))
        ref = cnn.forward_spectral(params, plan, x)
        out = cnn.forward_spectral(params, plan, x, backend="pallas_fused")
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-3, rtol=1e-3)

    def test_forced_halo_equals_forced_windowed(self):
        """The windowed path stays available as the halo oracle: forcing
        either mode produces identical logits."""
        cfg = vgg16_spectral.SMOKE
        params = cnn.init(KEY, cfg)
        x = jax.random.normal(jax.random.PRNGKey(2),
                              (1, 3, cfg.image_size, cfg.image_size))
        outs = {}
        for imode in df.INPUT_MODES:
            plan = build_network_plan(params, cfg, batch=1,
                                      input_mode=imode)
            assert all(lp.input_mode == imode for lp in plan.layers)
            outs[imode] = cnn.forward_spectral(params, plan, x,
                                               backend="pallas_fused")
        err = float(jnp.abs(outs["halo"] - outs["windowed"]).max())
        assert err <= 1e-6, err

    def test_bad_input_mode_raises(self):
        cfg = vgg16_spectral.SMOKE
        params = cnn.init(KEY, cfg)
        with pytest.raises(ValueError, match="input_mode"):
            build_network_plan(params, cfg, input_mode="nope")
