"""Element-granular scheduled sparse Hadamard inside the fused kernel.

Covers the PR-4 tentpole: Alg-2 INDEX/VALUE tables compiled per layer
(``scheduler.compile_layer_tables``), executed inside the single
pallas_call (``fused_spectral_pipeline_scheduled``), selected per layer
by the mode-aware cost model / autotuner, and precompiled into the
LayerPlan — built once, reused forever (monkeypatch-enforced, same
style as tests/test_plan.py).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import vgg16_spectral
from repro.core import autotune, dataflow as df
from repro.core import scheduler as sch
from repro.core import sparse as sp
from repro.core import spectral as spec
from repro.core.plan import build_network_plan
from repro.kernels.fused_spectral_conv import (
    FLOWS, fused_spectral_conv2d_scheduled)
from repro.models import cnn

KEY = jax.random.PRNGKey(0)
VGG = {layer.name: layer for layer in df.VGG16_LAYERS}


def _layer_case(h=13, w=12, cin=4, cout=6, alpha=4.0, seed=3):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((2, cin, h, w)), jnp.float32)
    wk = jnp.asarray(rng.standard_normal((cout, cin, 3, 3)), jnp.float32)
    b = jnp.asarray(0.1 * rng.standard_normal(cout), jnp.float32)
    geo = spec.make_geometry(h, w, 3, 8)
    sk = sp.prune_magnitude(spec.spectral_kernel(wk, 8), alpha)
    return x, sk, b, geo


class TestScheduledKernelParity:
    """Scheduled-fused == masked-einsum oracle, all flows, <= 1e-5."""

    @pytest.mark.parametrize("flow", FLOWS)
    @pytest.mark.parametrize("alpha", [2.0, 4.0, 8.0])
    def test_vs_einsum_oracle(self, flow, alpha):
        x, sk, b, geo = _layer_case(alpha=alpha)
        y = fused_spectral_conv2d_scheduled(
            x, sk, geo, n_par=4, r=6, flow=flow, block_m=2, block_p=8,
            bias=b, relu=True)
        y_ref = jax.nn.relu(
            spec.spectral_conv2d_pretransformed(x, sk, geo)
            + b[None, :, None, None])
        err = float(jnp.abs(y - y_ref).max())
        assert err <= 1e-5, (flow, alpha, err)

    def test_flows_agree(self):
        x, sk, b, geo = _layer_case(alpha=4.0, seed=5)
        outs = [fused_spectral_conv2d_scheduled(
            x, sk, geo, n_par=4, r=6, flow=fl, block_m=2, block_p=8,
            bias=b) for fl in FLOWS]
        for y in outs[1:]:
            np.testing.assert_allclose(np.asarray(y), np.asarray(outs[0]),
                                       atol=1e-5, rtol=1e-5)

    def test_group_remainder_and_oversized_blocks(self):
        """c_out not a multiple of n_par; blocks larger than dims."""
        x, sk, b, geo = _layer_case(cout=7, alpha=4.0, seed=9)
        y = fused_spectral_conv2d_scheduled(
            x, sk, geo, n_par=3, r=6, block_m=512, block_p=512, bias=b)
        y_ref = (spec.spectral_conv2d_pretransformed(x, sk, geo)
                 + b[None, :, None, None])
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   atol=1e-5, rtol=1e-5)


class TestScheduledPlanParity:
    """Plan-level: forward_spectral executes the scheduled datapath."""

    def test_per_layer_alphas_with_dense_fallback(self):
        """Acceptance: scheduled-fused == einsum oracle <= 1e-5 across
        per-layer alphas, including the alpha=1 layer that must fall
        back to the dense plane datapath."""
        alphas = tuple([1.0, 2.0] + [4.0] * 11)
        cfg = dataclasses.replace(vgg16_spectral.SMOKE, alpha=alphas)
        params = cnn.init(KEY, cfg)
        for i, conv in enumerate(params["convs"]):
            conv["b"] = 0.1 * jax.random.normal(
                jax.random.PRNGKey(i), conv["b"].shape)
        plan = build_network_plan(params, cfg, batch=1,
                                  hadamard="scheduled")
        assert plan.layers[0].hadamard == "dense"     # alpha=1 fallback
        assert plan.layers[0].tables is None
        assert all(lp.hadamard == "scheduled" and lp.tables is not None
                   for lp in plan.layers[1:])
        x = jax.random.normal(KEY, (1, 3, cfg.image_size, cfg.image_size))
        ref = cnn.forward_spectral(params, plan, x, backend="einsum")
        out = cnn.forward_spectral(params, plan, x,
                                   backend="pallas_fused")
        err = float(jnp.abs(out - ref).max())
        assert err <= 1e-5, err

    def test_auto_mode_plan_runs_and_records_modes(self):
        cfg = vgg16_spectral.SMOKE
        params = cnn.init(KEY, cfg)
        plan = build_network_plan(params, cfg, batch=1)   # hadamard=auto
        for lp in plan.layers:
            assert lp.hadamard in df.HADAMARD_MODES
            assert (lp.tables is not None) == (lp.hadamard == "scheduled")
            assert lp.hadamard == lp.tuning.hadamard
        x = jax.random.normal(KEY, (1, 3, cfg.image_size, cfg.image_size))
        ref = cnn.forward_spectral(params, plan, x, backend="einsum")
        out = cnn.forward_spectral(params, plan, x,
                                   backend="pallas_fused")
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-4, rtol=1e-4)

    def test_exact_schedule_stats_for_scheduled_layers(self):
        cfg = vgg16_spectral.SMOKE
        params = cnn.init(KEY, cfg)
        plan = build_network_plan(params, cfg, batch=1,
                                  hadamard="scheduled")
        for lp in plan.layers:
            if lp.hadamard != "scheduled":
                continue
            assert lp.schedule_cycles is not None
            assert 0.0 < lp.pe_utilization <= 1.0
            assert lp.stats()["table_bytes"] == lp.tables.nbytes > 0


class TestTablesBuiltOnce:
    """Satellite: scheduled tables are compiled at plan-build time and
    REUSED — no scheduling work ever runs inside a forward pass."""

    def test_forward_never_recompiles_tables(self, monkeypatch):
        cfg = vgg16_spectral.SMOKE
        params = cnn.init(KEY, cfg)
        plan = build_network_plan(params, cfg, batch=2,
                                  hadamard="scheduled")
        assert any(lp.hadamard == "scheduled" for lp in plan.layers)

        def boom(name):
            def _raise(*a, **k):
                raise AssertionError(f"{name} called inside forward")
            return _raise

        monkeypatch.setattr(sch, "compile_layer_tables",
                            boom("compile_layer_tables"))
        monkeypatch.setattr(sch, "schedule_exact_cover",
                            boom("schedule_exact_cover"))
        monkeypatch.setattr(sch, "build_tables", boom("build_tables"))

        x = jax.random.normal(KEY, (2, 3, cfg.image_size, cfg.image_size))
        for _ in range(2):                 # second call: jit cache hit
            out = cnn.forward_spectral(params, plan, x,
                                       backend="pallas_fused")
            assert bool(jnp.isfinite(out).all())


class TestCompileLayerTables:
    def test_shapes_padding_and_remap(self):
        rng = np.random.default_rng(0)
        wf = jnp.asarray(rng.standard_normal((6, 5, 8, 8))
                         + 1j * rng.standard_normal((6, 5, 8, 8)))
        sk = sp.prune_magnitude(wf, 16.0)
        active = sp.compacted_active_bins(sk)
        assert active is not None          # high alpha leaves empty bins
        vals = np.asarray(sk.values).reshape(6, 5, 64)
        lt = sch.compile_layer_tables(np.asarray(sk.indices), vals, 64,
                                      r=6, n_par=4, active=active,
                                      m_pad_to=3)
        assert lt.n_groups == 2 and lt.n_par == 4      # ceil(6/4)
        assert lt.m_pad == 6                           # 5 -> pad_to 3
        assert lt.idx.max() < len(active)              # compacted coords
        assert np.all(lt.vr[:, 5:] == 0)               # padded channels
        assert 0.0 < lt.pe_utilization <= 1.0
        assert lt.total_cycles > 0
        # exact cover: every non-zero weight appears exactly once
        got = np.sort(np.abs(lt.vr + 1j * lt.vi)[np.abs(
            lt.vr + 1j * lt.vi) > 0])
        want = np.sort(np.abs(vals)[np.abs(vals) > 0])
        np.testing.assert_allclose(got, want, rtol=1e-6)

    def test_dense_coordinates_when_no_active_set(self):
        rng = np.random.default_rng(1)
        wf = jnp.asarray(rng.standard_normal((4, 2, 8, 8))
                         + 1j * rng.standard_normal((4, 2, 8, 8)))
        sk = sp.prune_magnitude(wf, 4.0)
        vals = np.asarray(sk.values).reshape(4, 2, 64)
        lt = sch.compile_layer_tables(np.asarray(sk.indices), vals, 64,
                                      r=8, n_par=4, active=None)
        assert lt.idx.max() < 64


class TestModeAwareCostModel:
    def test_scheduled_kernel_bytes_le_bin_on_vgg16(self):
        """Acceptance: scheduled kernel-operand HBM bytes <= the
        bin-compacted plane stream on every sparse VGG16 layer."""
        for layer in df.VGG16_LAYERS:
            kw = dict(batch=1, active_bins=None)
            bin_c = df.tpu_fused_flow_cost(layer, 8, 4.0, 64, 128, 64,
                                           "output_stationary",
                                           hadamard="bin", **kw)
            sched = df.tpu_fused_flow_cost(layer, 8, 4.0, 64, 128, 64,
                                           "output_stationary",
                                           hadamard="scheduled", **kw)
            assert (sched["kernel_hbm_bytes"]
                    <= bin_c["kernel_hbm_bytes"]), layer.name

    def test_mode_flops_ordering(self):
        """bin MACs scale with Fa <= K^2 <= dense; scheduled counts the
        HONEST one-hot realization, above the paper's element count."""
        layer = df.VGG16_LAYERS[5]
        c = {m: df.tpu_fused_flow_cost(layer, 8, 4.0, 64, 128, 64,
                                       "output_stationary", hadamard=m,
                                       active_bins=56)
             for m in df.HADAMARD_MODES}
        assert c["bin"]["had_flops"] < c["dense"]["had_flops"]
        t = layer.tiles(8)
        paper_elems = 8 * t * 16 * layer.c_in * layer.c_out
        assert c["scheduled"]["had_flops"] > paper_elems

    def test_legacy_default_unchanged(self):
        layer = df.VGG16_LAYERS[3]
        legacy = df.tpu_fused_flow_cost(layer, 8, 4.0, 64, 128, 64,
                                        "weight_stationary")
        nnz = 16
        want = layer.c_out * layer.c_in * nnz * 2 * 4
        assert legacy["kernel_hbm_bytes"] == want

    def test_bad_mode_raises(self):
        with pytest.raises(ValueError, match="hadamard"):
            df.tpu_fused_flow_cost(df.VGG16_LAYERS[0], 8, 4.0, 64, 128,
                                   64, "output_stationary",
                                   hadamard="nope")


class TestModeAwareAutotune:
    def test_mode_axis_returns_a_searched_mode(self):
        layer = df.VGG16_LAYERS[-1]
        tn = autotune.autotune_layer(layer, 8, 4.0,
                                     hadamard_modes=("bin", "scheduled"))
        assert tn.hadamard in ("bin", "scheduled")

    def test_legacy_call_has_no_mode(self):
        tn = autotune.autotune_layer(df.VGG16_LAYERS[3], 8, 4.0)
        assert tn.hadamard is None

    def test_late_layers_prefer_scheduled_early_prefer_planes(self):
        """The per-layer flexibility story, priced where the chip runs
        it: the tables save the most HBM bytes on the kernel-bound late
        layers, but the kernel expands them into planes on every grid
        step, and that expansion grows with the number of kernels.  So
        at batch 1 the planes win on all 13 layers, by the widest margin
        on the late ones (~12x on conv5_1) and the narrowest on conv1_1
        (192 kernels, ~3.5x)."""
        modes, ratio = {}, {}
        for layer in df.VGG16_LAYERS:
            tn = autotune.autotune_layer(
                layer, 8, 4.0, hadamard_modes=("bin", "scheduled"))
            modes[layer.name] = tn.hadamard
            by = {m: autotune.autotune_layer(
                layer, 8, 4.0, hadamard_modes=(m,)).predicted_s
                for m in ("bin", "scheduled")}
            ratio[layer.name] = by["scheduled"] / by["bin"]
        assert modes == {layer.name: "bin" for layer in df.VGG16_LAYERS}
        assert ratio["conv5_1"] > 10
        assert 2 < ratio["conv1_1"] < ratio["conv5_1"]


class TestTableExpansionCost:
    """The scheduled datapath's in-kernel table expansion, priced on
    the units that run it (``dataflow.TABLE_EXPAND_S``)."""

    @staticmethod
    def _cost(layer, bn, bp, bm, flow, mode="scheduled"):
        return df.tpu_fused_flow_cost(layer, 8, 4.0, bn, bp, bm, flow,
                                      hadamard=mode)

    @pytest.mark.parametrize("name", ["conv4_1", "conv4_2"])
    def test_auto_ranks_planes_ahead_of_tables_at_batch_1(self, name):
        """At VGG16 conv4_1 / conv4_2 shapes, B=1, the best plane
        candidate beats the best table candidate, so ``auto`` streams
        planes."""
        layer = VGG[name]
        kw = dict(batch=1, input_modes=df.INPUT_MODES)
        best = {m: autotune.autotune_layer(layer, 8, 4.0,
                                           hadamard_modes=(m,), **kw)
                for m in ("dense", "scheduled")}
        assert best["dense"].predicted_s < best["scheduled"].predicted_s
        auto = autotune.autotune_layer(
            layer, 8, 4.0, hadamard_modes=("dense", "scheduled"), **kw)
        assert auto.hadamard == "dense"

    def test_expansion_grows_with_lane_rounded_block_n(self):
        """N' rides the lanes: a 32-wide group pays for 128 lanes, so
        four times the groups cost four times the expansion."""
        layer = VGG["conv4_2"]
        for flow in df.FLOWS:
            narrow = self._cost(layer, 32, 128, 128, flow)
            wide = self._cost(layer, 128, 128, 128, flow)
            assert narrow["expand_s"] == pytest.approx(4 * wide["expand_s"])
            assert narrow["compute_s"] > wide["compute_s"]

    @pytest.mark.parametrize("flow", df.FLOWS)
    def test_expansion_repeats_on_every_p_step(self, flow):
        """Each grid step expands its table block: halving block_p on
        conv1_2 (1444 tiles: 6 -> 12 p steps) doubles the expansion."""
        layer = VGG["conv1_2"]
        few = self._cost(layer, 64, 256, 64, flow)
        many = self._cost(layer, 64, 128, 64, flow)
        assert many["grid_steps"] == 2 * few["grid_steps"]
        assert many["expand_s"] == pytest.approx(2 * few["expand_s"])

    def test_rate_covers_the_one_hot_dot(self):
        """The fitted rate is no less than the one-hot dot's MXU time
        alone: r of 128 rows of depth, 6 bf16 passes at HIGHEST."""
        assert df.TABLE_EXPAND_S >= 2 * df.LANES * 6 / df.TPU_PEAK_FLOPS

    def test_forced_scheduled_plan_uses_tables(self):
        """``auto`` builds no tables where the planes win; a forced
        'scheduled' plan still compiles its tables and runs them, equal
        to the plane plan."""
        cfg = vgg16_spectral.SMOKE
        params = cnn.init(KEY, cfg)
        auto = build_network_plan(params, cfg, batch=1)
        forced = build_network_plan(params, cfg, batch=1,
                                    hadamard="scheduled")
        assert all(lp.tables is None and lp.hadamard != "scheduled"
                   for lp in auto.layers)
        assert "tables" not in auto.phase_s
        assert all(lp.hadamard == "scheduled" and lp.tables is not None
                   for lp in forced.layers)
        x = jax.random.normal(KEY, (1, 3, cfg.image_size, cfg.image_size))
        np.testing.assert_allclose(
            np.asarray(cnn.forward_spectral(params, forced, x,
                                            backend="pallas_fused")),
            np.asarray(cnn.forward_spectral(params, auto, x,
                                            backend="pallas_fused")),
            atol=1e-4, rtol=1e-4)
