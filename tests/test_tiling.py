"""The TPU's (8, 128) tiling rule, checked on the CPU.

A block's last dim sits on the 128 lanes of a vreg and its second-last
on the 8 sublanes; Mosaic refuses a block that is neither a multiple of
those nor the array's full extent.  ``dataflow.tiling_violations`` states
the rule for the fused kernel; Alg 1 searches only blocks it admits and
``resilience.validate_layer_plan`` rejects any other, so an illegal plan
is caught here and never reaches the chip.
"""

import dataclasses

import jax
import pytest

from repro.configs import resnet18_spectral, vgg16_spectral
from repro.core import autotune as at
from repro.core import dataflow as df
from repro.core import resilience as res
from repro.core import spectral as spec
from repro.core.plan import build_network_plan
from repro.models import cnn

MODELS = {"vgg16": vgg16_spectral.CONFIG, "resnet18": resnet18_spectral.CONFIG}


@pytest.mark.parametrize("batch", [1, 8])
@pytest.mark.parametrize("model", sorted(MODELS))
def test_alg1_emits_only_legal_blocks(model, batch):
    for layer in MODELS[model].layers:
        tn = at.autotune_layer(layer, 8, 4.0, batch=batch,
                               hadamard_modes=["dense", "scheduled"],
                               input_modes=df.INPUT_MODES)
        assert not df.tiling_violations(
            layer, 8, tn.block_n, tn.block_m, tn.block_p,
            hadamard=tn.hadamard, input_mode=tn.input_mode), (layer, tn)
        if tn.input_mode == "windowed":
            assert tn.block_p % df.LANES == 0, tn


def test_alg1_pads_small_tile_counts_to_a_lane_tile():
    """conv5 has 9 tiles per image: the plan records the 128-lane block
    the kernel streams, never block_p=9."""
    layer = next(l for l in df.VGG16_LAYERS if l.name == "conv5_1")
    tn = at.autotune_layer(layer, 8, 4.0, batch=1,
                           hadamard_modes=["dense", "scheduled"],
                           input_modes=["windowed"])
    assert tn.block_p == df.LANES


@pytest.fixture(scope="module")
def wide_plan():
    """One 128 -> 128 channel layer on 8x8 pixels, dense planes."""
    layer = df.ConvLayer("wide", 128, 128, 8, 8)
    cfg = cnn.SpectralCNNConfig(name="wide", layers=(layer,),
                                image_size=8, pool_after=frozenset(),
                                fc_dim=8, n_classes=4)
    params = cnn.init(jax.random.PRNGKey(0), cfg)
    return build_network_plan(params, cfg, hadamard="dense",
                              input_mode="windowed")


def test_validate_flags_block_m_off_the_lane_tile(wide_plan):
    lp = wide_plan.layers[0]
    assert not [d for d in res.validate_layer_plan(lp)
                if d.check == "tiling"]
    bad = dataclasses.replace(
        lp, tuning=dataclasses.replace(lp.tuning, block_m=64))
    diags = [d for d in res.validate_layer_plan(bad) if d.check == "tiling"]
    assert diags and all(d.severity == "error" for d in diags)
    assert "block_m=64" in diags[0].message


def test_validate_flags_block_n_off_the_sublane_tile(wide_plan):
    lp = wide_plan.layers[0]
    bad = dataclasses.replace(
        lp, tuning=dataclasses.replace(lp.tuning, block_n=12))
    assert any(d.check == "tiling" and d.severity == "error"
               for d in res.validate_layer_plan(bad))


def test_harden_demotes_an_illegal_block(wide_plan):
    lp = wide_plan.layers[0]
    bad = dataclasses.replace(
        lp, tuning=dataclasses.replace(lp.tuning, block_m=64))
    plan = dataclasses.replace(wide_plan, layers=(bad,))
    hardened = res.harden_network_plan(plan, probe=False)
    assert hardened.layers[0] is not bad
    assert hardened.layers[0].provenance


@pytest.mark.parametrize("h,block_p", [(224, 38), (224, 5 * 38),
                                       (56, 10), (64, 11), (112, 19)])
def test_halo_row_blocks_start_on_sublane_tiles(h, block_p):
    """Split along H, every halo block starts on a multiple of 8 rows
    and spans a multiple of 8, and its windows still cover the tiles."""
    geo = spec.make_geometry(h, h, 3, 8)
    hg = spec.halo_block_geometry(geo, block_p)
    assert hg.nbh > 1
    sh, _ = spec.halo_block_starts(geo, hg)
    assert (sh % spec.ROW_ALIGN == 0).all()
    assert hg.rh % spec.ROW_ALIGN == 0 or hg.rh == geo.h_in
    gr, _ = spec.halo_gather_matrices(geo, hg)
    # every in-image window row is selected exactly once
    assert gr.sum() == sum(
        1 for i in range(geo.n_tiles_h) for kh in range(8)
        if 0 <= i * geo.tile - 2 + kh < geo.h_in)


def test_halo_rows_of_an_unaligned_height_are_flagged():
    """28 rows split in blocks: the last block's clamped start (28 - rh)
    cannot be a multiple of 8, so the split is illegal — a single block
    (the full height) is the legal halo read."""
    layer = df.ConvLayer("c", 256, 256, 28, 28)
    geo = spec.make_geometry(28, 28, 3, 8)
    assert df.tiling_violations(layer, 8, 256, 256, geo.n_tiles_w,
                                hadamard="dense", input_mode="halo")
    assert not df.tiling_violations(layer, 8, 256, 256, geo.n_tiles,
                                    hadamard="dense", input_mode="halo")
