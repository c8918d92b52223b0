"""Resilient execution layer: plan validation, taxonomy, health report.

The companion fault-injection ladder walk lives in test_faults.py; this
module covers the STATIC half — ``validate_plan`` invariants, the error
taxonomy's back-compat contract, corrupted Alg-2 tables rejected at plan
BUILD time (not kernel launch), the hypothesis property that the Alg-2
compiler's own output always validates, and ``health_report``.
"""

import dataclasses
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import dataflow as df
from repro.core import resilience as res
from repro.core import scheduler as sch
from repro.core import sparse as sp
from repro.models import cnn
from repro.testing import faults

MINI_LAYERS = (
    df.ConvLayer("c1", 3, 8, 32, 32),
    df.ConvLayer("c2", 8, 8, 16, 16),
    df.ConvLayer("c3", 8, 8, 8, 8),
)
MINI = cnn.SpectralCNNConfig(
    name="mini-res", layers=MINI_LAYERS, alpha=4.0, n_classes=4,
    image_size=32, fc_dim=8, pool_after=frozenset({"c1", "c2", "c3"}))


@pytest.fixture(scope="module")
def mini_params():
    return cnn.init(jax.random.PRNGKey(0), MINI)


@pytest.fixture(scope="module")
def mini_plan(mini_params):
    return cnn.build_plan(mini_params, MINI, batch=1,
                          hadamard="scheduled", input_mode="halo")


# ---------------------------------------------------------------------------
# Error taxonomy
# ---------------------------------------------------------------------------

def test_taxonomy_backcompat_subclassing():
    """Structured errors must keep pre-taxonomy except clauses working:
    validation errors are ValueErrors, lowering errors are
    NotImplementedErrors (the old _check_hw_safe contract)."""
    assert issubclass(res.PlanValidationError, ValueError)
    assert issubclass(res.KernelLoweringError, NotImplementedError)
    assert issubclass(res.NumericGuardError, ValueError)
    for klass in (res.PlanValidationError, res.KernelLoweringError,
                  res.NumericGuardError):
        assert issubclass(klass, res.ResilienceError)


def test_error_carries_structure():
    diags = [res.Diagnostic("c1", "tables/idx-bounds", "boom"),
             res.Diagnostic("c2", "vmem-budget", "big", "warn")]
    err = res.PlanValidationError("plan failed", layer="c1",
                                  site="validate_plan", diagnostics=diags)
    assert err.layer == "c1" and err.site == "validate_plan"
    assert len(err.diagnostics) == 2
    msg = str(err)
    assert "tables/idx-bounds" in msg and "[c2] vmem-budget" in msg


def test_dma_accumulator_geometry_validated(mini_plan):
    """PR 8 replaces the hardware-safety gate with manual-DMA
    accumulator geometry checks: a healthy plan carries no dma/*
    errors, a degenerate block size is caught at validate time, and
    split-p weight-stationary (illegal pre-PR-8) is now clean."""
    for lp in mini_plan.layers:
        diags = res.validate_layer_plan(lp, batch=mini_plan.batch)
        assert not [d for d in diags if d.check.startswith("dma/")
                    and d.severity == "error"]
    lp = mini_plan.layers[0]
    bad = dataclasses.replace(
        lp, tuning=dataclasses.replace(lp.tuning, block_n=0))
    diags = res.validate_layer_plan(bad)
    assert any(d.check == "dma/tile-bounds" and d.severity == "error"
               for d in diags)
    split = dataclasses.replace(
        lp, tuning=dataclasses.replace(lp.tuning,
                                       flow="weight_stationary",
                                       block_p=lp.geo.n_tiles_w))
    diags = res.validate_layer_plan(split)
    assert not [d for d in diags if d.severity == "error"]


def test_guard_policy_validated():
    with pytest.raises(ValueError):
        res.NumericGuards(policy="explode")


# ---------------------------------------------------------------------------
# Plan validation
# ---------------------------------------------------------------------------

def test_validate_plan_healthy(mini_plan):
    """A freshly built plan has no error-severity diagnostics on the
    scheduled+halo datapath (the most aggressive variant)."""
    diags = res.validate_plan(mini_plan)
    assert not [d for d in diags if d.severity == "error"]


def test_build_plan_validates_by_default(mini_params):
    """build_network_plan runs validate_plan unless told not to."""
    plan = cnn.build_plan(mini_params, MINI, batch=1, validate=False)
    assert res.validate_plan(plan, raise_on_error=False) is not None
    # default path already validated mini_plan without raising


def test_oob_index_rejected_at_plan_build_not_launch(mini_plan):
    """A mutated OOB INDEX table is rejected by static validation —
    before any kernel launch could gather against the bad address."""
    bad = faults.corrupt_plan_tables(mini_plan, kind="oob_index")
    with pytest.raises(res.PlanValidationError) as ei:
        res.validate_plan(bad)
    err = ei.value
    assert err.site == "validate_plan"
    checks = {d.check for d in err.diagnostics}
    assert "tables/idx-bounds" in checks
    assert str(faults.OOB_INDEX) in str(err)
    # the failing layer is named — no traceback archaeology needed
    assert err.layer in {lp.layer.name for lp in mini_plan.layers}


def test_corrupt_value_is_invisible_to_static_validation(mini_plan):
    """A finite-but-wrong VALUE plane passes the static validator —
    catching it is the runtime parity guard's job (test_faults.py)."""
    bad = faults.corrupt_plan_tables(mini_plan, kind="corrupt_value")
    diags = res.validate_plan(bad)
    assert not [d for d in diags if d.severity == "error"]


def test_validate_layer_plan_flags_bad_modes(mini_plan):
    lp = dataclasses.replace(mini_plan.layers[0], input_mode="telepathy")
    diags = res.validate_layer_plan(lp)
    assert any(d.check == "modes/input" for d in diags)
    lp2 = dataclasses.replace(mini_plan.layers[0], backend="quantum")
    diags2 = res.validate_layer_plan(lp2)
    assert any(d.check == "modes/backend" for d in diags2)


def test_validate_layer_plan_flags_bad_bias(mini_plan):
    lp = mini_plan.layers[0]
    bad_bias = jnp.asarray(np.full((1, lp.layer.c_out), np.nan,
                                   np.float32))
    lp = dataclasses.replace(lp, bias=bad_bias)
    diags = res.validate_layer_plan(lp)
    assert any(d.check == "epilogue/bias-finite" for d in diags)


def test_vmem_budget_is_warn_severity(mini_plan):
    """An over-budget working set is advisory (the autotuner's
    documented smallest-footprint fallback), not a hard error — but it
    must be reported."""
    diags = res.validate_plan(mini_plan, vmem_budget=1)
    vmem = [d for d in diags if d.check == "vmem-budget"]
    assert vmem and all(d.severity == "warn" for d in vmem)


# ---------------------------------------------------------------------------
# Property: the Alg-2 compiler's own output always validates
# ---------------------------------------------------------------------------

@settings(max_examples=20, deadline=None)
@given(
    n_kernels=st.sampled_from([4, 8, 16]),
    m_ch=st.sampled_from([3, 4, 8]),
    alpha=st.sampled_from([2.0, 4.0]),
    block_m=st.sampled_from([4, 8, 16]),
    seed=st.integers(0, 2**31 - 1),
)
def test_compiled_tables_always_validate(n_kernels, m_ch, alpha,
                                         block_m, seed):
    """Property: for any random sparsity pattern, the tables
    ``scheduler.compile_layer_tables`` emits pass ``validate_tables``
    clean — bounds, dtypes, shape alignment, padding.  The validator
    rejects only *corrupted* tables, never fresh ones."""
    k = 8
    rng = np.random.default_rng(seed)
    w = (rng.standard_normal((n_kernels, m_ch, k, k))
         + 1j * rng.standard_normal((n_kernels, m_ch, k, k))
         ).astype(np.complex64)
    sk = sp.prune_random(w, alpha, seed=seed)
    active = sp.compacted_active_bins(sk)
    n_bins = len(active) if active is not None else k * k
    bm = min(block_m, m_ch)
    tables = sch.compile_layer_tables(
        np.asarray(sk.indices), np.asarray(sk.values).reshape(
            n_kernels, m_ch, k * k),
        k * k, df.SCHEDULE_R, n_par=min(8, n_kernels),
        active=active, m_pad_to=bm)
    diags = res.validate_tables(
        tables, n_bins=n_bins, r=df.SCHEDULE_R, c_out=n_kernels,
        c_in=m_ch, block_m=block_m, layer="prop")
    assert not diags, [str(d) for d in diags]


def test_validate_tables_catches_all_corruptions(mini_plan):
    """Each corruption class maps to its named check."""
    lp = next(l for l in mini_plan.layers if l.tables is not None)
    kw = dict(n_bins=lp.n_active_bins, r=df.SCHEDULE_R,
              c_out=lp.layer.c_out, c_in=lp.layer.c_in,
              block_m=lp.tuning.block_m, layer=lp.layer.name)
    tb = lp.tables

    def checks(**overrides):
        fields = {"idx": tb.idx, "sel": tb.sel, "vr": tb.vr,
                  "vi": tb.vi}
        fields.update(overrides)
        mut = types.SimpleNamespace(**fields)
        return {d.check for d in res.validate_tables(mut, **kw)}

    assert not checks()                                   # pristine
    bad_idx = np.array(tb.idx, copy=True)
    bad_idx.flat[0] = -3
    assert "tables/idx-bounds" in checks(idx=bad_idx)
    bad_sel = np.array(tb.sel, copy=True)
    bad_sel.flat[0] = 10**6
    assert "tables/sel-bounds" in checks(sel=bad_sel)
    bad_vr = np.array(tb.vr, copy=True)
    bad_vr.flat[0] = np.inf
    assert "tables/value-finite" in checks(vr=bad_vr)
    assert "tables/idx-dtype" in checks(
        idx=np.asarray(tb.idx, np.int64))


# ---------------------------------------------------------------------------
# Health report + harden on a healthy plan
# ---------------------------------------------------------------------------

def test_health_report_healthy(mini_plan):
    hr = mini_plan.health_report()
    assert hr["healthy"] is True
    assert hr["demoted_layers"] == []
    assert hr["issues"]["error"] == 0
    # rows key by stable node id over the execution DAG: one row per
    # conv layer PLUS one per pool node
    conv_rows = [r for r in hr["layers"] if r["kind"] == "conv"]
    assert len(conv_rows) == len(mini_plan.layers)
    row = conv_rows[0]
    for key in ("node", "layer", "backend", "flow", "hadamard",
                "input_mode", "demotions"):
        assert key in row
    assert row["backend"] == "fused" and row["demotions"] == []


def test_harden_is_noop_on_healthy_plan(mini_plan):
    """No fault installed: every layer keeps its chosen variant and no
    provenance is recorded."""
    hard = res.harden_network_plan(mini_plan)
    assert all(not lp.provenance for lp in hard.layers)
    assert [(lp.input_mode, lp.hadamard, lp.backend)
            for lp in hard.layers] == \
           [(lp.input_mode, lp.hadamard, lp.backend)
            for lp in mini_plan.layers]


def test_stats_surface_backend_and_demotions(mini_plan):
    s = mini_plan.layers[0].stats()
    assert s["backend"] == "fused" and s["demotions"] == 0


# ---------------------------------------------------------------------------
# Backend-axis rungs (serving ladder)
# ---------------------------------------------------------------------------

def test_demote_layer_backend_walks_rungs(mini_plan):
    lp = mini_plan.layers[0]
    assert lp.backend == "fused"
    staged = res.demote_layer_backend(lp, reason="test")
    assert staged.backend == "staged"
    assert any("fused->staged" in p for p in staged.provenance)
    einsum = res.demote_layer_backend(staged, reason="test")
    assert einsum.backend == "einsum"
    # einsum is terminal: no further rung
    assert res.demote_layer_backend(einsum) is None
    # hadamard / input_mode untouched (backend axis only)
    assert (einsum.hadamard, einsum.input_mode) == \
        (lp.hadamard, lp.input_mode)


def test_plan_at_backend_rung(mini_plan):
    # already at the top rung: the very same object comes back
    assert res.plan_at_backend_rung(mini_plan, "fused") is mini_plan
    down = res.plan_at_backend_rung(mini_plan, "einsum",
                                    reason="load ladder")
    assert all(lp.backend == "einsum" for lp in down.layers)
    assert all(any("load ladder" in p for p in lp.provenance)
               for lp in down.layers)
    # idempotent: demoting an already-demoted plan is a no-op
    assert res.plan_at_backend_rung(down, "staged") is down
    with pytest.raises(ValueError):
        res.plan_at_backend_rung(mini_plan, "nonsense")


# ---------------------------------------------------------------------------
# Circuit breaker
# ---------------------------------------------------------------------------

class _Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def test_breaker_opens_after_consecutive_failures():
    clock = _Clock()
    brk = res.CircuitBreaker(name="fused", failure_threshold=3,
                             cooldown_s=1.0, clock=clock)
    assert brk.state == "closed" and brk.allow()
    brk.record_failure("boom")
    brk.record_failure("boom")
    assert brk.state == "closed"        # under threshold
    brk.record_failure("boom")
    assert brk.state == "open" and brk.n_opens == 1
    assert not brk.allow()              # cooldown not elapsed


def test_breaker_success_resets_failure_streak():
    brk = res.CircuitBreaker(name="fused", failure_threshold=2,
                             clock=_Clock())
    brk.record_failure("a")
    brk.record_success()                # streak broken
    brk.record_failure("b")
    assert brk.state == "closed"        # failures must be CONSECUTIVE
    brk.record_failure("c")
    assert brk.state == "open"


def test_breaker_half_open_to_closed_recovery():
    """The ISSUE-7 satellite: open -> (cooldown) -> half_open probe ->
    closed, with every transition recorded."""
    clock = _Clock()
    brk = res.CircuitBreaker(name="staged", failure_threshold=1,
                             cooldown_s=2.0, recovery_successes=1,
                             clock=clock)
    brk.record_failure("boom")
    assert brk.state == "open"
    assert not brk.allow()              # still cooling down
    clock.t = 5.0
    assert brk.allow()                  # cooldown elapsed: probe allowed
    assert brk.state == "half_open"
    brk.record_success()
    assert brk.state == "closed" and brk.failures == 0
    assert [t["to"] for t in brk.transitions] == \
        ["open", "half_open", "closed"]
    snap = brk.snapshot()
    assert snap["state"] == "closed" and snap["n_opens"] == 1


def test_breaker_half_open_failure_reopens():
    clock = _Clock()
    brk = res.CircuitBreaker(name="staged", failure_threshold=1,
                             cooldown_s=1.0, clock=clock)
    brk.record_failure("boom")
    clock.t = 2.0
    assert brk.allow() and brk.state == "half_open"
    brk.record_failure("still broken")
    assert brk.state == "open" and brk.n_opens == 2
    assert not brk.allow()              # fresh cooldown from reopen
    clock.t = 4.0
    assert brk.allow() and brk.state == "half_open"


# ---------------------------------------------------------------------------
# Plan cache (serving front end)
# ---------------------------------------------------------------------------

def test_plan_cache_warm_get_invalidate(mini_params):
    from repro.core.plan import PlanCache, plan_cache_key

    built = []

    def builder(params, cfg, *, batch, **kw):
        built.append((batch, tuple(sorted(kw))))
        return types.SimpleNamespace(batch=batch)

    cache = PlanCache(builder=builder)
    keys = cache.warm(mini_params, MINI, (1, 2))
    assert set(keys) == {1, 2} and len(cache) == 2
    assert cache.builds == 2 and cache.build_s >= 0.0
    # hits never touch the builder
    p1 = cache.get(mini_params, MINI, 1)
    assert p1.batch == 1 and cache.hits == 1 and cache.builds == 2
    # different build kwargs -> different entry, never a collision
    cache.get(mini_params, MINI, 1, hadamard="scheduled")
    assert cache.builds == 3 and len(cache) == 3
    # invalidation forces exactly one rebuild
    assert cache.invalidate(keys[1])
    assert not cache.invalidate(keys[1])        # already gone
    cache.get(mini_params, MINI, 1)
    assert cache.builds == 4 and cache.invalidations == 1
    st = cache.stats()
    assert st["entries"] == 3 and st["builds"] == 4
    # scalar vs per-layer alpha normalize to the same key
    seq = dataclasses.replace(MINI, alpha=(4.0,) * len(MINI.layers))
    assert plan_cache_key(MINI, 1) == plan_cache_key(seq, 1)
