"""Deterministic fault injection for the resilience layer.

The degradation ladder (``core.resilience``) exists for failures we
cannot reproduce off-hardware: Mosaic lowering errors, VMEM
RESOURCE_EXHAUSTED, silently corrupted Alg-2 tables.  This module makes
those failures *reproducible*: ``inject(site, ...)`` installs a fault at
one of the named sites production code consults
(``resilience.fault_check`` / ``fault_corrupt``), so tests drive every
edge of the ladder with plain CPU runs.

Sites (``FAULT_SITES``):

  'lowering'        raise at kernel dispatch — simulates a Mosaic
                    lowering/compile failure of the chosen variant.
                    Match kwargs (e.g. ``input_mode='halo'``,
                    ``hadamard='scheduled'``, ``backend='fused'``)
                    restrict which variants fail, selecting WHICH rung
                    of the ladder the probe exercises.
  'vmem_overflow'   raise at kernel dispatch with a RESOURCE_EXHAUSTED-
                    style RuntimeError — simulates the VMEM OOM real
                    hardware produces for over-budget blocks.
  'oob_index'       corrupt the Alg-2 INDEX table during
                    ``scheduler.compile_layer_tables`` (an entry pushed
                    far out of the active-bin range) — must be caught
                    by plan validation at BUILD time.
  'corrupt_value'   corrupt the Alg-2 VALUE plane (finite but wrong) —
                    invisible to static validation, caught by the
                    runtime parity guard.
  'nan_activations' corrupt a fused layer's output with a NaN — caught
                    by the runtime NaN/Inf scan.
  'shard_tables'    shard-scoped fault in a SHARDED plan (match on
                    ``shard=<int>``, ``layer=...``, ``strategy=...``).
                    Raise-site by default (one shard's operands fail to
                    stage); pass ``corrupt=`` to mutate that shard's
                    staged Alg-2 tables instead.  Consulted host-side —
                    operand staging in ``distributed.executor`` and the
                    probe in ``resilience.harden_sharded_plan`` — so an
                    injected shard fault surfaces as a structured
                    demotion BEFORE any device enters a collective
                    (never as a mesh hang).

Serve-level sites (consulted by ``launch.spectral_serve``):

  'serve_kernel'     raise at batch dispatch inside the serving loop —
                     a kernel fault mid-request.  Match on
                     ``backend='fused'|'staged'|'einsum'`` (the ladder
                     rung being attempted) and/or ``bucket=<int>``.
                     Drives the per-backend circuit breaker and the
                     in-batch retry a rung down.
  'serve_plan_cache' corrupt the NetworkPlan fetched from the serving
                     plan cache (default: one scheduled layer's Alg-2
                     INDEX table pushed out of range via
                     ``corrupt_plan_tables`` — the plan must contain a
                     scheduled layer, e.g. built with
                     ``hadamard='scheduled'``).  The server must catch
                     it with ``validate_plan`` on fetch and serve via
                     the einsum terminal rung (which never reads the
                     tables) — never execute it silently.
  'serve_slow'       add ``SLOW_EXTRA_S`` seconds to a batch's service
                     time (advancing the server's virtual clock when it
                     has one) — creates deadline pressure without
                     wall-clock sleeps.

Usage::

    from repro.testing import faults

    with faults.inject("lowering", input_mode="halo") as fault:
        plan = resilience.harden_network_plan(plan)   # halo -> windowed
    assert fault.fires > 0

Faults are matched on the call-site context and removed when the
context manager exits; nesting composes (all active faults are
consulted).  Everything is deterministic — no randomness, no wall
clock.
"""

from __future__ import annotations

import contextlib
from typing import Callable, Iterator

import numpy as np

from repro.core import resilience as res

FAULT_SITES = res.FAULT_SITES

# A value far outside any active-bin range (K^2 <= 64 in this repo).
OOB_INDEX = 1_000_000
# Injected extra service seconds for 'serve_slow' — large relative to
# any test deadline, small enough that a soak stays fast.
SLOW_EXTRA_S = 0.25
# Finite perturbation of one VALUE entry: large enough that the sampled
# parity guard (default tol 1e-4) trips on channel 0, small enough to
# stay finite through the whole net.
VALUE_DELTA = 32.0


def _default_exc(site: str, match: dict) -> Callable[[], Exception]:
    """Raw, un-taxonomized errors — like the real failures they mimic.
    The resilience layer must translate them into structured ones."""
    if site == "vmem_overflow":
        return lambda: RuntimeError(
            "RESOURCE_EXHAUSTED: Ran out of memory in memory space "
            f"vmem (injected fault, match={match})")
    if site == "serve_kernel":
        return lambda: RuntimeError(
            f"kernel fault mid-request (injected fault, match={match})")
    return lambda: RuntimeError(
        f"Mosaic lowering failed (injected fault at {site!r}, "
        f"match={match})")


def _corrupt_oob_index(idx):
    out = np.array(idx, copy=True)
    out.flat[0] = OOB_INDEX
    return out


def _corrupt_value(vr):
    out = np.array(vr, copy=True)
    out.flat[0] += VALUE_DELTA
    return out


def _corrupt_nan(y):
    import jax.numpy as jnp
    return y.at[(0,) * y.ndim].set(jnp.nan)


def _corrupt_served_plan(plan):
    # OOB INDEX corruption: loud to validate_plan, invisible to the
    # einsum rung (which consumes pruned kernels, never the tables) —
    # so the server's corruption fallback stays oracle-exact.
    return corrupt_plan_tables(plan, kind="oob_index")


def _corrupt_slow(dt):
    return float(dt) + SLOW_EXTRA_S


_DEFAULT_CORRUPT = {
    "oob_index": _corrupt_oob_index,
    "corrupt_value": _corrupt_value,
    "nan_activations": _corrupt_nan,
    "serve_plan_cache": _corrupt_served_plan,
    "serve_slow": _corrupt_slow,
}


@contextlib.contextmanager
def inject(site: str, *, exc: Callable[[], Exception] | None = None,
           corrupt: Callable | None = None,
           **match) -> Iterator[res.InjectedFault]:
    """Install one deterministic fault at ``site`` for the duration of
    the ``with`` block.

    ``match`` kwargs restrict the fault to call sites whose context
    carries every key with an equal value (see module doc).  ``exc``
    overrides the raised exception factory for raise-sites;
    ``corrupt`` overrides the value transform for corruption-sites.
    Yields the ``InjectedFault`` so tests can assert ``fault.fires``.
    """
    if site in ("lowering", "vmem_overflow", "serve_kernel"):
        fault = res.InjectedFault(site=site, match=dict(match),
                                  exc=exc or _default_exc(site, match))
    elif site == "shard_tables":
        # dual-use: raise-site unless a corruption transform is given
        if corrupt is not None:
            fault = res.InjectedFault(site=site, match=dict(match),
                                      corrupt=corrupt)
        else:
            fault = res.InjectedFault(site=site, match=dict(match),
                                      exc=exc or _default_exc(site, match))
    elif site in _DEFAULT_CORRUPT:
        fault = res.InjectedFault(site=site, match=dict(match),
                                  corrupt=corrupt or _DEFAULT_CORRUPT[site])
    else:
        raise ValueError(f"unknown fault site {site!r}; must be one of "
                         f"{FAULT_SITES}")
    res.install_fault(fault)
    try:
        yield fault
    finally:
        res.remove_fault(fault)


def corrupt_plan_tables(plan, *, layer: str | None = None,
                        kind: str = "oob_index"):
    """Return a copy of ``plan`` with one scheduled layer's Alg-2 tables
    mutated (``kind`` in 'oob_index' | 'corrupt_value') — for direct
    tests that a corrupted built plan is rejected by ``validate_plan``.
    """
    import dataclasses

    import jax.numpy as jnp

    from repro.core.plan import PlanTables, stack_shard_operands

    mutate = _DEFAULT_CORRUPT[kind]
    new_layers = []
    done = False
    for lp in plan.layers:
        eligible = (lp.tables is not None
                    and (layer is None or lp.layer.name == layer))
        if eligible and not done:
            tb = lp.tables
            if kind == "oob_index":
                tb = PlanTables(jnp.asarray(mutate(tb.idx)), tb.sel,
                                tb.vr, tb.vi)
            else:
                tb = PlanTables(tb.idx, tb.sel,
                                jnp.asarray(mutate(tb.vr)), tb.vi)
            lp = dataclasses.replace(lp, tables=tb)
            done = True
        new_layers.append(lp)
    if not done:
        raise ValueError(f"no scheduled layer matching {layer!r} in plan")
    return dataclasses.replace(plan, layers=tuple(new_layers))


def chaos_soak(*, cfg=None, queue_limit: int = 16, seed: int = 0,
               oracle_tol: float = 1e-5, log=None) -> dict:
    """Deterministic fault-injected soak of ``launch.spectral_serve``.

    Submits >= 4x ``queue_limit`` requests in bursts while walking the
    server through every serve-level fault site on a virtual clock:

      wave 1  2x-capacity burst (excess MUST be shed) with
              ``serve_kernel`` faults on the staged rung — the load
              ladder demotes under queue pressure, the staged breaker
              opens, batches retry a rung down in-flight;
      wave 2  1x burst through a ``serve_plan_cache`` corruption window
              — corrupt plans must be caught on fetch and served via
              the einsum terminal rung;
      wave 3  tight-deadline requests stuck behind a ``serve_slow``
              window — they MUST retire ``deadline_exceeded``, never
              execute late;
      wave 4  clean recovery burst after cooldown — the ladder promotes
              back to fused and serves on it.

    Gates (report ``failed_gates`` must be empty; the serve-bench CI
    job exits nonzero otherwise):

      all_terminal                every request reached a terminal code
      zero_loop_deaths            no tick exception ever killed a loop
      shed_nonzero                overload was shed, not queued
      deadline_exceeded_nonzero   expired requests retired structurally
      demotion_and_promotion      >= 1 load demotion AND >= 1 promotion
      kernel_faults_exercised     the serve_kernel site actually fired
      plan_cache_corruption_exercised / slow_injection_exercised
      recovered_to_fused          final rung is the fast path again
      no_silent_wrong_answers     every 'ok' logits row within
                                  ``oracle_tol`` of the einsum oracle

    Returns the full report dict (gates, stats, health_report).
    """
    import jax.numpy as jnp

    from repro.launch import spectral_serve as ss
    from repro.models import cnn

    if cfg is None:
        from repro.configs import vgg16_spectral
        cfg = vgg16_spectral.SMOKE
    say = log or (lambda *_: None)
    clock = ss.ManualClock()
    srv = ss.SpectralServer(
        cfg, queue_limit=queue_limit, clock=clock, seed=seed,
        plan_kwargs={"hadamard": "scheduled"},
        demote_pressure=0.75, promote_pressure=0.25,
        demote_patience=1, promote_patience=2,
        breaker_failures=2, breaker_cooldown_s=0.5)

    reqs: list = []

    def burst(n: int, deadline_s: float | None = None) -> None:
        wave = ss.synthetic_requests(n, cfg, seed=seed + len(reqs),
                                     deadline_s=deadline_s,
                                     rid0=len(reqs))
        for r in wave:
            srv.submit(r)
        reqs.extend(wave)

    def drive(n: int, dt: float = 0.05) -> None:
        for _ in range(n):
            try:
                srv.tick()
            except Exception as e:        # noqa: BLE001 — soak must live
                srv.loop_deaths += 1
                say(f"loop death: {type(e).__name__}: {e}")
            clock.advance(dt)

    say(f"wave 1: 2x burst ({2 * queue_limit}) + staged kernel faults")
    burst(2 * queue_limit)
    with inject("serve_kernel", backend="staged"):
        drive(2)
    drive(4)  # faults cleared; idle ticks let the ladder promote

    say(f"wave 2: 1x burst ({queue_limit}) + plan-cache corruption")
    burst(queue_limit)
    with inject("serve_plan_cache"):
        drive(1)

    say("wave 3: tight deadlines behind a slow-service window")
    burst(queue_limit // 2, deadline_s=0.01)
    burst(queue_limit // 2)        # clean requests behind the tight ones
    clock.advance(0.05)            # tight deadlines expire while queued
    with inject("serve_slow"):
        drive(1)

    clock.advance(1.0)  # past the breaker cooldown
    srv.run_until_drained(max_ticks=20 * queue_limit)
    for _ in range(8 * srv.promote_patience):
        if srv._load_rung == 0 and not srv.queue:
            break
        drive(1, dt=0.1)

    say(f"wave 4: clean recovery burst ({queue_limit // 2}) on "
        f"rung {ss.SERVE_RUNGS[srv._load_rung]}")
    burst(queue_limit // 2)
    srv.run_until_drained(max_ticks=4 * queue_limit)

    stats = srv.stats()
    health = srv.health_report()

    # oracle parity for every completed answer, pristine plan, einsum
    ok_reqs = [r for r in reqs if r.ok]
    bucket = srv.buckets[-1]
    plan = srv.plans.get(srv.params, cfg, bucket, **srv.plan_kwargs)
    worst = 0.0
    for i in range(0, len(ok_reqs), bucket):
        chunk = ok_reqs[i:i + bucket]
        x = np.zeros((bucket,) + srv.image_shape, np.float32)
        for j, r in enumerate(chunk):
            x[j] = r.image
        ref = np.asarray(cnn.forward_spectral(srv.params, plan,
                                              jnp.asarray(x),
                                              backend="einsum"))
        for j, r in enumerate(chunk):
            worst = max(worst, float(np.max(np.abs(ref[j] - r.logits))))

    c = stats["counters"]
    gates = {
        "all_terminal": all(r.terminal for r in reqs),
        "zero_loop_deaths": stats["loop_deaths"] == 0,
        "shed_nonzero": c["overloaded"] > 0,
        "deadline_exceeded_nonzero": c["deadline_exceeded"] > 0,
        "demotion_and_promotion": (stats["demotions"] >= 1
                                   and stats["promotions"] >= 1),
        "kernel_faults_exercised": c["kernel_faults"] > 0,
        "plan_cache_corruption_exercised":
            c["plan_cache_corruptions"] > 0,
        "slow_injection_exercised": c["slow_injections"] > 0,
        "recovered_to_fused": health["rung"] == "fused",
        "no_silent_wrong_answers": worst <= oracle_tol,
    }
    failed = sorted(k for k, v in gates.items() if not v)
    say(f"{len(reqs)} requests: {c['ok']} ok / {c['overloaded']} shed "
        f"/ {c['deadline_exceeded']} deadline / {c['failed']} failed; "
        f"max |err| {worst:.2e}; failed gates: {failed or 'none'}")
    return {
        "requests": len(reqs),
        "queue_limit": queue_limit,
        "gates": gates,
        "failed_gates": failed,
        "oracle_max_abs_err": worst,
        "oracle_tol": oracle_tol,
        "stats": stats,
        "health": health,
    }


def corrupt_shard_tables(splan, *, layer: str | None = None,
                         shard: int = 0, kind: str = "oob_index"):
    """Return a copy of a ``ShardedNetworkPlan`` with ONE shard's Alg-2
    tables mutated (``kind`` in 'oob_index' | 'corrupt_value') — for
    direct tests that per-shard validation
    (``resilience.validate_sharded_plan``) catches a single rotten
    shard while its siblings stay healthy.
    """
    import dataclasses

    import jax.numpy as jnp

    from repro.core.plan import PlanTables, stack_shard_operands

    mutate = _DEFAULT_CORRUPT[kind]
    new_layers = []
    done = False
    for slp in splan.layers:
        eligible = (not done and len(slp.shards) > shard
                    and slp.shards[shard].tables is not None
                    and (layer is None or slp.base.layer.name == layer))
        if eligible:
            shards = list(slp.shards)
            sh = shards[shard]
            tb = sh.tables
            if kind == "oob_index":
                tb = PlanTables(jnp.asarray(mutate(tb.idx)), tb.sel,
                                tb.vr, tb.vi)
            else:
                tb = PlanTables(tb.idx, tb.sel,
                                jnp.asarray(mutate(tb.vr)), tb.vi)
            shards[shard] = dataclasses.replace(sh, tables=tb)
            slp = dataclasses.replace(
                slp, shards=tuple(shards),
                operands=(stack_shard_operands(shards)
                          if slp.strategy == "channel" else ()))
            done = True
        new_layers.append(slp)
    if not done:
        raise ValueError(
            f"no sharded layer matching layer={layer!r} with tables on "
            f"shard {shard} (build with hadamard='scheduled' and a "
            f"channel/spatial strategy)")
    return dataclasses.replace(splan, layers=tuple(new_layers))
