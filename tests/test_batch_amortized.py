"""Batch as an Alg-1 axis — the PR-8 repricing + manual-DMA kernels.

The fused kernel's manual-DMA psum accumulators make every
flow x input-mode combination legal at batch > 1, and the cost model
amortizes per-call kernel bytes over the batch, so the tuner's choice
is a real function of the serving bucket.  Covered here:

  * batch parity matrix: B in {1, 2, 4, 8} x 3 flows x 3 Hadamard
    modes stays <= 1e-5 of the einsum oracle (with the fused
    bias+ReLU epilogue) on the in-kernel halo path;
  * amortization monotonicity: per-image predicted cost is
    non-increasing along the doubling chain B in {1, 2, 4, 8} for
    every VGG16 layer — provable because ``_layer_candidates`` seeds
    the p-block axis with the doubling multiples of the per-image
    tile count, so every batch-B winner is reachable at batch 2B
    (property-based variant runs when hypothesis is installed);
  * the bucket axis is live: B=1 and B=8 tunings differ on at least
    one VGG16 layer (empirically: the conv5 block flips from
    output- to input-stationary once kernel bytes amortize).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import autotune, dataflow as df
from repro.core import sparse as sp
from repro.core import spectral as spec
from repro.kernels.fused_spectral_conv import (
    FLOWS, fused_spectral_conv2d, fused_spectral_conv2d_scheduled)

BATCHES = (1, 2, 4, 8)


def _case(batch, h=12, w=11, cin=3, cout=4, k=3, K=8, seed=7):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((batch, cin, h, w)), jnp.float32)
    wk = jnp.asarray(rng.standard_normal((cout, cin, k, k)), jnp.float32)
    b = jnp.asarray(0.1 * rng.standard_normal(cout), jnp.float32)
    geo = spec.make_geometry(h, w, k, K)
    return x, wk, b, geo


class TestBatchParityMatrix:
    """fused(halo) == oracle at every bucket, flows x Hadamard modes."""

    @pytest.mark.parametrize("batch", BATCHES)
    @pytest.mark.parametrize("flow", FLOWS)
    @pytest.mark.parametrize("mode", df.HADAMARD_MODES)
    def test_matrix(self, batch, flow, mode):
        x, wk, b, geo = _case(batch)
        sk = sp.prune_magnitude(spec.spectral_kernel(wk, 8), 4.0)
        if mode == "scheduled":
            y = fused_spectral_conv2d_scheduled(
                x, sk, geo, n_par=4, r=6, flow=flow, block_m=2,
                block_p=7, bias=b, relu=True, input_mode="halo")
        else:
            w_f = sk.values if mode == "dense" else sk
            y = fused_spectral_conv2d(
                x, w_f, geo, flow=flow, block_n=4, block_m=2,
                block_p=7, bias=b, relu=True, input_mode="halo")
        y_ref = jax.nn.relu(
            spec.spectral_conv2d_pretransformed(x, sk, geo)
            + b[None, :, None, None])
        err = float(jnp.abs(y - y_ref).max())
        assert err <= 1e-5, (batch, flow, mode, err)


def _best_per_image_s(layer, batch):
    tn = autotune.autotune_layer(layer, 8, 4.0, batch=batch,
                                 input_modes=df.INPUT_MODES)
    c = df.tpu_fused_flow_cost(layer, 8, 4.0, tn.block_n, tn.block_p,
                               tn.block_m, tn.flow, batch=batch,
                               input_mode=tn.input_mode or "windowed")
    return c["per_image_s"]


class TestAmortizationMonotone:
    """per-image predicted cost never rises along the doubling chain.

    Proof sketch the code must uphold: ``_layer_candidates`` always
    offers full-T p blocks for every doubling multiple of the
    per-image tile count, so any config priced at batch B is
    reachable at batch 2B, where the same blocks cost at most the sum
    of two batch-B calls (grid ceilings only merge) — hence the best
    per-image cost cannot increase.
    """

    @pytest.mark.parametrize(
        "layer", df.VGG16_LAYERS, ids=[l.name for l in df.VGG16_LAYERS])
    def test_vgg16_doubling_chain(self, layer):
        costs = [_best_per_image_s(layer, b) for b in BATCHES]
        for b_prev, b_next, c_prev, c_next in zip(
                BATCHES, BATCHES[1:], costs, costs[1:]):
            assert c_next <= c_prev * (1 + 1e-9), (
                layer.name, b_prev, b_next, c_prev, c_next)

    @settings(max_examples=25, deadline=None)
    @given(cin=st.sampled_from([3, 16, 64]),
           cout=st.sampled_from([8, 64, 256]),
           hw=st.sampled_from([14, 28, 56]))
    def test_random_layers(self, cin, cout, hw):
        layer = df.ConvLayer(f"rand{cin}x{cout}x{hw}", cin, cout, hw, hw)
        costs = [_best_per_image_s(layer, b) for b in BATCHES]
        for c_prev, c_next in zip(costs, costs[1:]):
            assert c_next <= c_prev * (1 + 1e-9), (layer.name, costs)

    def test_candidates_include_doubling_multiples(self):
        """The structural fact the proof rests on: at batch B the
        p-block axis offers t_img * 2^i for every 2^i <= B."""
        layer = df.VGG16_LAYERS[5]
        t_img = layer.tiles(8)
        for batch in BATCHES:
            bps = {bp for _, _, _, bp in autotune._layer_candidates(
                layer, 8, batch, autotune.BLOCK_CANDIDATES, True)}
            for i in range(batch.bit_length()):
                want = t_img * (1 << i)
                if want <= t_img * batch:
                    assert want in bps, (batch, want, sorted(bps))


class TestBucketAxisIsLive:
    def test_tuning_differs_between_b1_and_b8(self):
        """Batch must actually steer Alg 1: at least one VGG16 layer
        tunes differently at B=8 than at B=1 (kernel-byte amortization
        flips the conv5 block away from output-stationary)."""
        def key(tn):
            return (tn.flow, tn.block_n, tn.block_m, tn.block_p,
                    tn.input_mode)
        diffs = []
        for layer in df.VGG16_LAYERS:
            t1 = autotune.autotune_layer(layer, 8, 4.0, batch=1,
                                         input_modes=df.INPUT_MODES)
            t8 = autotune.autotune_layer(layer, 8, 4.0, batch=8,
                                         input_modes=df.INPUT_MODES)
            if key(t1) != key(t8):
                diffs.append((layer.name, key(t1), key(t8)))
        assert diffs, "B=1 and B=8 chose identical configs everywhere"

    def test_flow_flips_on_conv5(self):
        """The concrete amortization story from DATAFLOW.md S1b: conv5
        has 9 tiles per image, which one image pads to a whole 128-lane
        tile, so a batch of 8 rides the same lanes and the same kernel
        stream — its per-image predicted time falls more than 4x.  With
        the lane padding priced, both batches keep the kernel resident
        (input-stationary); unpadded, B=1 had picked another flow."""
        layer = next(l for l in df.VGG16_LAYERS if l.name == "conv5_1")
        t1 = autotune.autotune_layer(layer, 8, 4.0, batch=1,
                                     input_modes=df.INPUT_MODES)
        t8 = autotune.autotune_layer(layer, 8, 4.0, batch=8,
                                     input_modes=df.INPUT_MODES)
        assert (t1.flow, t8.flow) == ("input_stationary",) * 2, (t1, t8)
        assert t8.predicted_s / 8 < t1.predicted_s / 4, (t1, t8)
        assert t8.hbm_bytes / 8 < t1.hbm_bytes / 4, (t1, t8)
