"""Multi-device sharded spectral inference (ISSUE 9).

Four test families:

  1. Parity — channel- and spatial-sharded forward passes under
     ``shard_map`` vs the single-device einsum oracle, per layer across
     all 3 flows x 3 Hadamard modes and end-to-end on mixed-strategy
     networks.  The in-process tests need a multi-device mesh and skip
     on single-device hosts (the CI ``sharded`` job forces 8 host
     devices); a subprocess smoke test sets XLA_FLAGS itself so the
     default tier always exercises the collectives.
  2. Halo-exchange geometry — the cross-shard property suite: exactly
     k-1 raw rows cross each boundary (bit-exact), every shard-local
     gather selector stays in bounds, band windows equal the
     full-image windows bit for bit, and concatenated shard band
     canvases reconstruct the unsharded canvas to float-accumulation
     tolerance.  Runs under hypothesis when installed, plus a seeded
     deterministic sweep of the same property in every environment.
  3. Cache-key regression — ``plan_cache_key`` folds the mesh shape, so
     plans built for different meshes can never poison each other in a
     ``PlanCache`` (the silent-wrong-math hazard for spectral_serve).
  4. Shard-fault degradation — an injected per-shard fault
     ('shard_tables') is caught HOST-side by the hardening ladder and
     turns into a structured plan-level demotion, never a collective
     hang; a corrupted shard's tables are caught by per-shard
     validation while its siblings stay healthy.

All host-side tests (2-4) run on any machine: plan building,
validation, hardening and probing never enter a shard_map.
"""

from __future__ import annotations

import pathlib
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import autotune as at
from repro.core import dataflow as df
from repro.core import plan as pl
from repro.core import resilience as res
from repro.core import sparse as sp
from repro.core import spectral as spec
from repro.models import cnn
from repro.testing import faults

SRC = str(pathlib.Path(__file__).resolve().parents[1] / "src")

MULTI_DEVICE = len(jax.devices()) >= 2
needs_mesh = pytest.mark.skipif(
    not MULTI_DEVICE,
    reason="needs >= 2 devices (run under XLA_FLAGS="
           "--xla_force_host_platform_device_count=8)")


class TinyCfg:
    """2-layer spectral net small enough for interpret-mode sweeps:
    channel sharding feasible at D in {2, 4} (c_in 4 then 8), spatial
    at D <= 3 (n_tiles_h = 3 for 16x16 / fft 8 / k 3)."""
    name = "tiny-shard"
    fft_size = 8
    alpha = 4.0
    layers = (df.ConvLayer("c1", 4, 8, 16, 16, 3, 1),
              df.ConvLayer("c2", 8, 8, 16, 16, 3, 1))
    pool_after = frozenset({"c2"})


def _tiny_params(key):
    params = {"convs": []}
    for lay in TinyCfg.layers:
        k1, k2, key = jax.random.split(key, 3)
        params["convs"].append({
            "w": jax.random.normal(
                k1, (lay.c_out, lay.c_in, 3, 3), jnp.float32) * 0.1,
            "b": jax.random.normal(k2, (lay.c_out,), jnp.float32) * 0.1})
    feat = 8 * 8 * 8                    # c_out * (16/2)^2 after one pool
    k1, k2, k3, key = jax.random.split(key, 4)
    params["fc1"] = jax.random.normal(k1, (feat, 16), jnp.float32) * 0.05
    params["fc2"] = jax.random.normal(k2, (16, 16), jnp.float32) * 0.05
    params["fc3"] = jax.random.normal(k3, (16, 4), jnp.float32) * 0.05
    return params, key


# ---------------------------------------------------------------------------
# 1. Parity vs the single-device einsum oracle
# ---------------------------------------------------------------------------

@needs_mesh
class TestShardedParity:

    @pytest.fixture(scope="class")
    def net(self):
        params, key = _tiny_params(jax.random.PRNGKey(0))
        x = jax.random.normal(key, (2, 4, 16, 16), jnp.float32)
        base = pl.build_network_plan(params, TinyCfg, batch=2)
        ref = cnn.forward_spectral(params, base, x, backend="einsum")
        return params, x, base, ref

    @pytest.mark.parametrize("n_shards,strategies,extra", [
        (4, ("channel",), {}),
        (2, ("spatial",), {}),
        (2, None, {}),                       # two-level tuner decides
        (4, ("channel",), {"hadamard": "scheduled"}),
        (2, ("spatial",), {"hadamard": "scheduled"}),
        (2, ("spatial",), {"input_mode": "halo"}),
        (4, ("channel",), {"input_mode": "halo"}),
    ])
    def test_network_parity(self, net, n_shards, strategies, extra):
        from repro.distributed.executor import forward_spectral_sharded
        from repro.launch.mesh import make_spectral_mesh

        if len(jax.devices()) < n_shards:
            pytest.skip(f"needs {n_shards} devices")
        params, x, base, ref = net
        splan = pl.build_sharded_network_plan(
            params, TinyCfg, n_shards=n_shards, batch=2,
            strategies=strategies, **extra)
        mesh = make_spectral_mesh(n_shards)
        y = forward_spectral_sharded(params, splan, x, mesh=mesh,
                                     interpret=True)
        err = float(jnp.abs(y - ref).max())
        assert err <= 1e-5, (strategies, extra, err)
        if strategies is not None:
            # every layer where the forced strategy is feasible uses it
            want = strategies[0]
            for name, got in splan.strategies.items():
                layer = next(l for l in TinyCfg.layers if l.name == name)
                local = df.shard_local_layer(layer, TinyCfg.fft_size,
                                             n_shards, want)
                if local is not None:
                    assert got == want, (name, got)

    @pytest.mark.parametrize("flow", df.FLOWS)
    @pytest.mark.parametrize("hadamard", df.HADAMARD_MODES)
    @pytest.mark.parametrize("strategy", ("channel", "spatial"))
    def test_layer_parity_matrix(self, net, flow, hadamard, strategy):
        """Every (strategy, flow, Hadamard mode) cell of the shard-local
        kernel grid matches the einsum oracle <= 1e-5 on a real mesh."""
        from repro.distributed.executor import execute_sharded_layer
        from repro.launch.mesh import make_spectral_mesh

        params, x, base, _ = net
        # build the base under the matching forced Hadamard mode so the
        # base LayerPlan carries tables when the cell needs them
        plan = pl.build_network_plan(params, TinyCfg, batch=2,
                                     hadamard=hadamard)
        lp = plan.layers[0]                  # c1: 4 -> 8 channels
        if hadamard == "scheduled" and lp.hadamard != "scheduled":
            pytest.skip("schedule degenerated on this layer")
        n_shards = 2
        st = at.autotune_layer_sharded(
            lp.layer, plan.fft_size, lp.alpha, n_shards=n_shards,
            strategies=(strategy,), batch=2, flows=(flow,),
            hadamard_modes=[lp.hadamard],
            input_modes=[lp.input_mode or "windowed"],
            active_bins=(len(lp.active) if lp.active is not None
                         else None))
        assert st.strategy == strategy
        assert st.base.flow == flow
        slp = pl.make_sharded_layer_plan(lp, st, n_shards)
        assert slp.strategy == strategy and slp.shards
        mesh = make_spectral_mesh(n_shards)
        y = execute_sharded_layer(x, slp, mesh, interpret=True)
        y_ref = jax.nn.relu(
            spec.spectral_conv2d_pretransformed(x, lp.kernels, lp.geo)
            + jnp.reshape(lp.bias, (1, -1, 1, 1)))
        err = float(jnp.abs(y - y_ref).max())
        assert err <= 1e-5, (strategy, flow, hadamard, err)


def test_sharded_parity_subprocess_smoke():
    """Default-tier proof on any host: force an 8-device CPU mesh in a
    subprocess (XLA_FLAGS must precede the jax import) and check both
    collective strategies against the einsum oracle end to end."""
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = \
            "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp
        from repro.core import dataflow as df
        from repro.core import plan as pl
        from repro.distributed.executor import forward_spectral_sharded
        from repro.launch.mesh import make_spectral_mesh
        from repro.models import cnn

        class Cfg:
            name = "tiny-shard"
            fft_size = 8
            alpha = 4.0
            layers = (df.ConvLayer("c1", 4, 8, 16, 16, 3, 1),
                      df.ConvLayer("c2", 8, 8, 16, 16, 3, 1))
            pool_after = frozenset({"c2"})

        key = jax.random.PRNGKey(0)
        params = {"convs": []}
        for lay in Cfg.layers:
            k1, k2, key = jax.random.split(key, 3)
            params["convs"].append({
                "w": jax.random.normal(
                    k1, (lay.c_out, lay.c_in, 3, 3), jnp.float32) * 0.1,
                "b": jax.random.normal(k2, (lay.c_out,),
                                       jnp.float32) * 0.1})
        k1, k2, k3, key = jax.random.split(key, 4)
        params["fc1"] = jax.random.normal(k1, (512, 16),
                                          jnp.float32) * 0.05
        params["fc2"] = jax.random.normal(k2, (16, 16),
                                          jnp.float32) * 0.05
        params["fc3"] = jax.random.normal(k3, (16, 4),
                                          jnp.float32) * 0.05
        x = jax.random.normal(key, (2, 4, 16, 16), jnp.float32)
        base = pl.build_network_plan(params, Cfg, batch=2)
        ref = cnn.forward_spectral(params, base, x, backend="einsum")
        for D, strats in [(4, ("channel",)), (2, ("spatial",))]:
            mesh = make_spectral_mesh(D)
            splan = pl.build_sharded_network_plan(
                params, Cfg, n_shards=D, batch=2, strategies=strats,
                mesh=mesh)
            y = forward_spectral_sharded(params, splan, x, mesh=mesh,
                                         interpret=True)
            err = float(jnp.abs(y - ref).max())
            assert err <= 1e-5, (strats, err)
            # channel kernels are placed at build: one slice per device
            placed = [a for slp in splan.layers for a in slp.operands]
            assert bool(placed) == (strats == ("channel",))
            for a in placed:
                assert {s.device for s in a.addressable_shards} == set(
                    mesh.devices.flat)
                assert all(s.data.shape[0] == 1
                           for s in a.addressable_shards)
        print("SHARDED_PARITY_OK")
    """)
    r = subprocess.run([sys.executable, "-c", script],
                       env={"PYTHONPATH": SRC, "PATH": "/usr/bin:/bin",
                            "JAX_PLATFORMS": "cpu"},
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "SHARDED_PARITY_OK" in r.stdout


# ---------------------------------------------------------------------------
# 2. Cross-chip halo-exchange geometry (property test)
# ---------------------------------------------------------------------------

def _check_halo_property(h: int, ksize: int, fft_size: int,
                         n_shards: int, seed: int) -> None:
    """The pinned-down property for one (H, k, t, D) draw:

      a. the exchange ships EXACTLY k-1 raw rows per interior boundary
         (band d's halo == last k-1 rows of band d-1; zeros on shard 0)
         — BIT-exact, it is pure data movement;
      b. every shard-local gather selector indexes in bounds and each
         one-hot row has weight <= 1;
      c. concatenated shard band canvases reconstruct the unsharded
         full-conv canvas, and the global 'same' crop matches the
         unsharded oracle.  Checked to float-accumulation tolerance,
         not bit-exactly: the band inputs/windows ARE bit-identical,
         but XLA schedules the Hadamard contraction differently at
         band vs full tile extents (~1e-6 noise on identical inputs).
    """
    rng = np.random.default_rng(seed)
    w = h                                     # square images
    geo = spec.make_geometry(h, w, ksize, fft_size)
    if n_shards > geo.n_tiles_h:
        n_shards = geo.n_tiles_h              # keep the draw feasible
    ov = ksize - 1
    tr = spec.shard_band_rows(geo, n_shards)
    hb = tr * geo.tile
    c_in, c_out = 2, 3
    x = jnp.asarray(rng.standard_normal((1, c_in, h, w)), jnp.float32)
    wk = jnp.asarray(rng.standard_normal((c_out, c_in, ksize, ksize)),
                     jnp.float32)
    wf = spec.spectral_kernel(wk, fft_size)

    # a. exactly k-1 rows per boundary
    bands = spec.halo_exchange_reference(x, geo, n_shards)
    xp = np.zeros((1, c_in, n_shards * hb, w), np.float32)
    xp[:, :, :h] = np.asarray(x)
    for d, band in enumerate(bands):
        band = np.asarray(band)
        assert band.shape[2] == ov + hb, (d, band.shape)
        if d == 0:
            assert not band[:, :, :ov].any()
        else:
            np.testing.assert_array_equal(
                band[:, :, :ov],
                xp[:, :, d * hb - ov: d * hb])
        np.testing.assert_array_equal(
            band[:, :, ov:], xp[:, :, d * hb:(d + 1) * hb])

    # b. shard-local gather selectors in bounds
    bgeo = spec.make_band_geometry(geo, tr)
    for block_p in (1, 4, 16):
        hg = spec.halo_block_geometry(bgeo, block_p)
        sh, sw = spec.halo_block_starts(bgeo, hg)
        assert (sh >= 0).all() and (sh + hg.rh <= bgeo.h_in).all()
        assert (sw >= 0).all() and (sw + hg.rw <= bgeo.w_in).all()
        gr, gc = spec.halo_gather_matrices(bgeo, hg)
        for g in (gr, gc):
            s = g.sum(axis=-1)
            assert ((s == 0) | (s == 1)).all()   # one-hot or zero-pad

    # c. reconstruction of the unsharded canvas (tolerance: see
    #    docstring — the windows are bit-identical, the contraction's
    #    schedule is not)
    full = _full_canvas(x, wf, geo)
    parts = [spec.spectral_band_conv2d_pretransformed(b, wf, bgeo)
             for b in bands]
    stitched = jnp.concatenate(parts, axis=2)[:, :, :geo.h_pad]
    np.testing.assert_allclose(np.asarray(stitched), np.asarray(full),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(
        np.asarray(spec.crop_canvas_same(stitched, geo)),
        np.asarray(spec.spectral_conv2d_pretransformed(x, wf, geo)),
        rtol=1e-4, atol=1e-4)
    # ... and the windows themselves ARE bit-identical: every band
    # tile equals the corresponding full-image overlap-save window.
    win_full = np.asarray(spec.extract_tiles_overlapping(x, geo))
    b_, m_ = win_full.shape[:2]
    k = geo.fft_size
    wins = [np.asarray(spec.extract_tiles_overlapping(bd, bgeo))
            .reshape(b_, m_, tr, geo.n_tiles_w, k, k) for bd in bands]
    win_cat = np.concatenate(wins, axis=2)[:, :, :geo.n_tiles_h]
    np.testing.assert_array_equal(
        win_cat.reshape(win_full.shape), win_full)


def _full_canvas(x, wf, geo):
    """Unsharded uncropped full-conv canvas via the same einsum path
    the band oracle uses (windows -> FFT -> Hadamard -> IFFT -> valid
    corner -> canvas relayout)."""
    windows = spec.extract_tiles_overlapping(x, geo)
    x_f = jnp.fft.fft2(windows.astype(jnp.float32))
    y_f = jnp.einsum("bmtuv,nmuv->bntuv", x_f, wf)
    y_sp = jnp.fft.ifft2(y_f).real
    ov = geo.ksize - 1
    return spec.assemble_tile_canvas(
        y_sp[..., ov:, ov:].astype(jnp.float32), geo)


# hypothesis explores the draw space when installed; conftest.py ships
# a stub when it is not, so this test SKIPS (never fails to import) on
# bare images — the deterministic sweep below carries the property
# unconditionally in every environment.
from hypothesis import given, settings
from hypothesis import strategies as hst


@settings(max_examples=20, deadline=None)
@given(h=hst.integers(8, 40), ksize=hst.sampled_from([3, 5]),
       fft_size=hst.sampled_from([8]),
       n_shards=hst.integers(2, 5),
       seed=hst.integers(0, 2 ** 16))
def test_halo_exchange_geometry(h, ksize, fft_size, n_shards, seed):
    _check_halo_property(h, ksize, fft_size, n_shards, seed)


@pytest.mark.parametrize("case", range(20))
def test_halo_exchange_geometry_sweep(case):
    """Seeded deterministic sweep of the same property — runs whether
    or not hypothesis is installed (the @given twin skips under the
    conftest stub)."""
    rng = np.random.default_rng(1234 + case)
    h = int(rng.integers(8, 41))
    ksize = int(rng.choice([3, 5]))
    n_shards = int(rng.integers(2, 6))
    _check_halo_property(h, ksize, 8, n_shards,
                         seed=int(rng.integers(0, 2 ** 16)))


# ---------------------------------------------------------------------------
# 3. Mesh-aware plan-cache keys (regression)
# ---------------------------------------------------------------------------

class TestMeshCacheKey:

    def test_key_folds_mesh_shape(self):
        cfg = TinyCfg
        k_none = pl.plan_cache_key(cfg, 1)
        k1 = pl.plan_cache_key(cfg, 1, mesh_shape=(1,))
        k4 = pl.plan_cache_key(cfg, 1, mesh_shape=(4,))
        k8 = pl.plan_cache_key(cfg, 1, mesh_shape=(8,))
        k24 = pl.plan_cache_key(cfg, 1, mesh_shape=(2, 4))
        assert len({k_none, k1, k4, k8, k24}) == 5
        # same mesh -> same key, list/tuple normalized
        assert k4 == pl.plan_cache_key(cfg, 1, mesh_shape=[4])

    def test_plan_cache_separates_meshes(self):
        built = []

        def builder(params, cfg, *, batch, **kw):
            built.append(batch)
            return ("plan", len(built))

        cache = pl.PlanCache(builder=builder)
        a = cache.get({}, TinyCfg, 1)
        b = cache.get({}, TinyCfg, 1, mesh_shape=(8,))
        c = cache.get({}, TinyCfg, 1, mesh_shape=(4,))
        assert len(built) == 3                # one build per mesh
        assert a != b and b != c
        # hits on re-get, still per mesh
        assert cache.get({}, TinyCfg, 1, mesh_shape=(8,)) == b
        assert cache.get({}, TinyCfg, 1) == a
        assert len(built) == 3
        assert cache.stats()["hits"] == 2

    def test_sharded_plan_reuses_a_given_base(self):
        """A base plan built once serves every strategy's sharded plan
        (full-size builds take minutes); the partitioning is the same
        as a fresh build's, and a base of another batch is refused."""
        params, _ = _tiny_params(jax.random.PRNGKey(0))
        base = pl.build_network_plan(params, TinyCfg, batch=2)
        for strategies in (("channel",), ("spatial",), None):
            splan = pl.build_sharded_network_plan(
                params, TinyCfg, n_shards=2, batch=2,
                strategies=strategies, base=base)
            fresh = pl.build_sharded_network_plan(
                params, TinyCfg, n_shards=2, batch=2,
                strategies=strategies)
            assert splan.base is base
            assert all(s.base is lp
                       for s, lp in zip(splan.layers, base.layers))
            assert splan.strategies == fresh.strategies
        with pytest.raises(ValueError, match="batch"):
            pl.build_sharded_network_plan(params, TinyCfg, n_shards=2,
                                          batch=1, base=base)

    def test_server_threads_mesh_shape(self):
        """SpectralServer must key every cache access by its mesh."""
        import inspect

        from repro.launch.spectral_serve import SpectralServer
        sig = inspect.signature(SpectralServer.__init__)
        assert "mesh_shape" in sig.parameters


# ---------------------------------------------------------------------------
# 4. Shard-scoped faults: structured demotion, not a collective hang
# ---------------------------------------------------------------------------

class TestShardFaultDegradation:

    @pytest.fixture(scope="class")
    def splan(self):
        params, _ = _tiny_params(jax.random.PRNGKey(0))
        return pl.build_sharded_network_plan(
            params, TinyCfg, n_shards=2, batch=2,
            strategies=("channel",))

    def test_healthy_plan_hardens_to_itself(self, splan):
        out = res.harden_sharded_plan(splan, interpret=True)
        assert [s.strategy for s in out.layers] \
            == [s.strategy for s in splan.layers]
        assert all(not s.provenance for s in out.layers)

    def test_injected_shard_fault_demotes_structurally(self, splan):
        """A fault pinned to ONE shard of ONE layer makes the hardening
        ladder demote that layer's BASE plan (plan-level, uniform across
        devices) — the plan that comes back has non-empty provenance and
        a degraded rung, and the walk terminates (no hang: everything is
        host-side)."""
        name = splan.layers[0].base.layer.name
        with faults.inject("shard_tables", layer=name, shard=1) as fault:
            out = res.harden_sharded_plan(splan, interpret=True)
        assert fault.fires > 0
        demoted = out.layers[0]
        assert demoted.provenance, "demotion must be recorded"
        base0 = splan.layers[0].base
        rung_moved = (
            demoted.base.backend != base0.backend
            or demoted.base.hadamard != base0.hadamard
            or demoted.base.input_mode != base0.input_mode
            or demoted.strategy != splan.layers[0].strategy)
        assert rung_moved
        # untouched layers keep their strategy and stay clean
        assert out.layers[1].strategy == splan.layers[1].strategy
        assert not out.layers[1].provenance

    def test_persistent_shard_fault_collapses_to_replicate(self, splan):
        """A fault that keeps firing at the fused shard kernels walks
        the layer to a non-fused backend, whose sharded form is
        'replicate' — the terminal rung that never enters a shard_map."""
        name = splan.layers[0].base.layer.name
        with faults.inject("shard_tables", layer=name) as fault:
            out = res.harden_sharded_plan(splan, interpret=True)
        assert fault.fires > 0
        demoted = out.layers[0]
        # the fault matches any shard of the layer, so demotion walks
        # until the layer leaves the fused backend entirely
        assert demoted.strategy == "replicate"
        assert demoted.base.backend != "fused"
        assert not demoted.shards

    def test_corrupt_shard_tables_caught_by_validation(self):
        params, _ = _tiny_params(jax.random.PRNGKey(0))
        splan = pl.build_sharded_network_plan(
            params, TinyCfg, n_shards=2, batch=2,
            strategies=("channel",), hadamard="scheduled")
        assert any(s.shards and s.shards[0].tables is not None
                   for s in splan.layers), "need a scheduled layer"
        bad = faults.corrupt_shard_tables(splan, shard=1,
                                          kind="oob_index")
        with pytest.raises(res.PlanValidationError):
            res.validate_sharded_plan(bad)
        diags = res.validate_sharded_plan(bad, raise_on_error=False)
        assert any(d.severity == "error" for d in diags)
        # siblings stay healthy: the unmodified plan still validates
        res.validate_sharded_plan(splan)
