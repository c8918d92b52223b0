"""Overload-resilient spectral serving front end (images in, logits out).

A request-queue server over the compile-once LayerPlan stack
(``core.plan.build_network_plan`` + ``models.cnn.forward_spectral``)
built around one principle: **a latency number only matters if it holds
at the tail, under bursty load and partial failure**.  The paper's
figure of merit is single-stream latency; this module is what keeps
that figure meaningful when requests arrive faster than they drain.

Four mechanisms compose:

  1. **Admission control + load shedding.**  The queue is bounded
     (``queue_limit``); a request that arrives to a full queue is
     rejected *immediately* with a structured ``overloaded`` response
     instead of queuing unboundedly.  Every request carries an optional
     relative deadline; a request still queued past its deadline
     retires with ``deadline_exceeded`` before ever touching a kernel.
     Every request reaches exactly one terminal response code:
     ``ok`` | ``overloaded`` | ``deadline_exceeded`` | ``failed``.

  2. **Batch bucketing over a keyed plan cache.**  Pending requests are
     batched into the smallest bucket of ``buckets`` (default
     {1, 2, 4, 8}) that fits, padded with zero images, and executed
     with a ``NetworkPlan`` cached per (config, alpha, bucket) in a
     ``core.plan.PlanCache`` warmed at startup — no request ever pays
     the plan build (``plan_build_s``: 133-138 s per bucket for full
     VGG16 and ResNet-18 on a TPU v5e host, see PERF.md).
     Plans are tuned *at* their bucket's batch with the interpret-mode
     per-step overhead priced in (``dataflow.INTERPRET_STEP_S``), so
     the batch-8 bucket gets batch-8 blocks instead of inheriting
     batch-1 choices (PR 8).  Dispatch is double-buffered: while the
     current batch's kernels run, the *next* batch's padded input is
     already being uploaded (``jax.device_put`` is async), so the
     host->device copy overlaps kernel time instead of serializing
     ahead of it — ``staged_uploads``/``staged_hits`` counters surface
     the overlap in ``health_report()``.

  3. **A load-triggered degradation ladder.**  The PR-6 ladder demoted
     layers on *faults*; here the same backend rungs
     (``resilience.BACKEND_RUNGS``: fused -> staged -> einsum, demoted
     via ``plan_at_backend_rung`` with provenance) are driven by
     *load*: a pressure signal (queue-depth fill fraction max'd with
     the fraction of queued requests whose deadline slack is below the
     current service-time estimate) demotes execution one rung after
     ``demote_patience`` high-pressure ticks and promotes one rung back
     after ``promote_patience`` low-pressure ticks.  Independently, a
     per-backend ``resilience.CircuitBreaker`` (consecutive-failure
     open, half-open recovery probes) skips rungs that keep failing, so
     a kernel fault mid-request costs one in-batch retry a rung down —
     never a dead loop.  Every rung transition and breaker state change
     is surfaced in ``health_report()``.

  4. **Deterministic chaos sites.**  The server consults three
     serve-level fault sites (``repro.testing.faults``):
     ``serve_kernel`` (raise at batch dispatch on a matching backend),
     ``serve_plan_cache`` (corrupt the plan fetched from the cache —
     caught by ``validate_plan`` on fetch, served via the einsum
     terminal rung, never executed silently), and ``serve_slow``
     (inject extra seconds of service time, creating deadline
     pressure).  ``faults.chaos_soak`` drives a 4x-capacity burst
     through all of them; ``benchmarks/serve_bench.py --chaos`` gates
     CI on it.

Run a synthetic burst from the CLI::

    PYTHONPATH=src python -m repro.launch.spectral_serve --requests 32 \
        --queue-limit 8 --json -

Timing is injectable (``clock=``, any zero-arg callable returning
seconds; ``ManualClock`` for deterministic tests) so deadlines, breaker
cooldowns and the ladder are all testable without wall-clock sleeps.

Tracing.  The server, the forward walk and the plan build always carry
profiler spans (``repro.core.spans``; ~1 us each while no trace is
taken).  ``jax.profiler.start_trace(dir)`` ... ``stop_trace()`` around a
running server captures them on the host timeline beside the chip's
executions:

  ``serve.submit`` (``rid``);
  ``serve.tick`` (``rid`` of the batch's first request, ``n``,
  ``bucket``, ``rung``), holding ``serve.take``, ``serve.upload``
  (``staged_hit``), ``serve.plan``, ``serve.forward``,
  ``serve.stage_next``, ``serve.readback`` and ``serve.finish``;
  inside ``serve.forward`` one ``forward.node`` per graph node
  (``node``, ``kind``; conv nodes also ``hadamard``, ``flow``,
  ``input_mode``, ``residual``, ``backend``, ``predicted_us``) and
  ``forward.fc_head``.

A device execution belongs to the ``forward.node`` span in which the
host enqueued it (its ``run_id``).  The plan build is not on the request
path; its seconds per phase (prune, operators, schedule_stats,
autotune, tables, validate) are counted in
``health_report()["plan_cache"]["phase_s"]`` and ``stats()
["plan_phase_s"]``.
"""

from __future__ import annotations

import argparse
import collections
import dataclasses
import json
import logging
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import dataflow as df
from repro.core import resilience as res
from repro.core import spans
from repro.core.plan import PlanCache, plan_cache_key
from repro.models import cnn

_LOG = logging.getLogger("repro.spectral_serve")

#: Terminal response codes — every submitted request ends on exactly one.
RESPONSE_CODES = ("ok", "overloaded", "deadline_exceeded", "failed")

#: Default batch buckets (requests are padded up to the nearest).
DEFAULT_BUCKETS = (1, 2, 4, 8)

SERVE_RUNGS = res.BACKEND_RUNGS          # ("fused", "staged", "einsum")


class ManualClock:
    """Deterministic virtual clock: callable like ``time.monotonic``,
    advanced explicitly (tests) or by injected ``serve_slow`` seconds
    (the server calls ``advance`` when its clock supports it)."""

    def __init__(self, t0: float = 0.0):
        self.t = float(t0)

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += float(dt)


@dataclasses.dataclass
class InferenceRequest:
    """One image-classification request.

    ``deadline_s`` is a *relative* latency budget from submission (None
    = the server default; the default default is unlimited).  On
    completion exactly one of the terminal ``code`` values is set; for
    ``ok`` the class ``logits`` and the serving ``rung`` (backend that
    produced them) are filled in.
    """

    rid: int
    image: np.ndarray                     # [C, H, W] f32
    deadline_s: float | None = None
    submitted_at: float | None = None
    completed_at: float | None = None
    code: str | None = None               # terminal response code
    logits: np.ndarray | None = None
    error: str | None = None
    rung: str | None = None               # backend that served it

    @property
    def terminal(self) -> bool:
        return self.code is not None

    @property
    def ok(self) -> bool:
        return self.code == "ok"

    @property
    def latency_s(self) -> float | None:
        if self.completed_at is None or self.submitted_at is None:
            return None
        return self.completed_at - self.submitted_at

    def response(self) -> dict:
        """The structured wire response (logits elided for failures)."""
        out = {"rid": self.rid, "code": self.code}
        if self.code == "ok":
            out["rung"] = self.rung
            out["latency_s"] = self.latency_s
        elif self.error:
            out["error"] = self.error
        return out


class SpectralServer:
    """Bounded-queue batch-bucketing server over the LayerPlan stack.

    See the module docstring for the mechanism overview.  The main
    loop is ``tick()`` (expire -> ladder update -> batch -> execute);
    ``run_until_drained`` drives it to completion plus a bounded
    cool-down so the ladder can promote back once pressure clears.
    """

    def __init__(self, cfg=None, *,
                 buckets=DEFAULT_BUCKETS,
                 queue_limit: int = 16,
                 default_deadline_s: float | None = None,
                 demote_pressure: float = 0.8,
                 promote_pressure: float = 0.25,
                 demote_patience: int = 1,
                 promote_patience: int = 2,
                 breaker_failures: int = 3,
                 breaker_cooldown_s: float = 1.0,
                 clock=time.monotonic,
                 seed: int = 0,
                 warm: bool = True,
                 warm_forward: bool = False,
                 guards: res.NumericGuards | None = None,
                 interpret: bool | None = None,
                 plan_cache: PlanCache | None = None,
                 plan_kwargs: dict | None = None,
                 mesh_shape: tuple[int, ...] | None = None):
        if cfg is None:
            from repro.configs import vgg16_spectral
            cfg = vgg16_spectral.SMOKE
        self.cfg = cfg
        self.buckets = tuple(sorted(int(b) for b in buckets))
        if not self.buckets:
            raise ValueError("need at least one batch bucket")
        self.max_bucket = self.buckets[-1]
        self.queue_limit = int(queue_limit)
        self.default_deadline_s = default_deadline_s
        self.demote_pressure = demote_pressure
        self.promote_pressure = promote_pressure
        self.demote_patience = int(demote_patience)
        self.promote_patience = int(promote_patience)
        self.clock = clock
        self.interpret = interpret
        self.guards = guards
        self.plan_kwargs = dict(plan_kwargs or {})
        # Per-bucket plans should minimize the wall clock of the backend
        # that actually runs; everywhere but real TPU that is the
        # interpret-mode kernel, whose time is dominated by grid steps.
        if interpret is not False:
            self.plan_kwargs.setdefault("step_overhead_s",
                                        df.INTERPRET_STEP_S)

        first = list(cfg.layers)[0]
        self.image_shape = (first.c_in, first.h_in, first.w_in)
        self.params = cnn.init(jax.random.PRNGKey(seed), cfg)

        # The device topology this server executes on, folded into every
        # plan-cache key.  A cache shared across servers (or a server
        # whose mesh changed across restarts with a persistent cache)
        # must never hand a plan built for one topology to another —
        # sharded plans bake shard geometry and collective shapes, so a
        # cross-mesh hit is silent wrong math, not an error.
        self.mesh_shape = (tuple(int(d) for d in mesh_shape)
                           if mesh_shape is not None else None)
        self.plans = plan_cache if plan_cache is not None else PlanCache()
        if warm:
            self.plans.warm(self.params, cfg, self.buckets,
                            mesh_shape=self.mesh_shape,
                            **self.plan_kwargs)

        # per-rung circuit breakers; the terminal einsum rung is never
        # gated (it must always execute)
        self.breakers: dict[str, res.CircuitBreaker] = {
            b: res.CircuitBreaker(name=b,
                                  failure_threshold=breaker_failures,
                                  cooldown_s=breaker_cooldown_s,
                                  clock=self.clock)
            for b in SERVE_RUNGS[:-1]}

        self.queue: collections.deque[InferenceRequest] = collections.deque()
        self._staged: dict | None = None   # next batch's in-flight upload
        self._variants: dict[int, dict] = {}
        self._validated_plan: dict[int, object] = {}
        self._corrupt_buckets: set[int] = set()
        self._service_ema: dict[str, float] = {}

        self._load_rung = 0
        self._demote_streak = 0
        self._promote_streak = 0
        self._last_pressure = {"pressure": 0.0, "queue_fill": 0.0,
                               "deadline_risk": 0.0, "queue_depth": 0}
        self.transitions: list[dict] = []
        self.n_demotions = 0
        self.n_promotions = 0

        self._ticks = 0
        self.batches = 0
        self.loop_deaths = 0
        self.latencies: list[float] = []
        self.served_by = {b: 0 for b in SERVE_RUNGS}
        self.counters = {c: 0 for c in ("submitted",) + RESPONSE_CODES}
        self.counters.update(kernel_faults=0, plan_cache_corruptions=0,
                             slow_injections=0, staged_uploads=0,
                             staged_hits=0)
        self._first_submit_t: float | None = None
        self._last_completion_t: float | None = None

        if warm_forward and warm:
            self.warm_forward()

    # -- plumbing ------------------------------------------------------

    def _now(self) -> float:
        return self.clock()

    def warm_forward(self) -> None:
        """Run one zero batch per bucket at the fused rung so no
        request pays trace/compile time either."""
        for b in self.buckets:
            plan = self.plans.get(self.params, self.cfg, b,
                                  mesh_shape=self.mesh_shape,
                                  **self.plan_kwargs)
            x = jnp.zeros((b,) + self.image_shape, jnp.float32)
            jax.block_until_ready(cnn.forward_spectral(
                self.params, plan, x, backend="pallas_fused",
                interpret=self.interpret))

    # -- admission control --------------------------------------------

    def submit(self, req: InferenceRequest) -> InferenceRequest:
        """Admit one request, or shed it with a structured response.

        Returns the request with either ``submitted_at`` set (queued)
        or a terminal ``overloaded`` / ``failed`` code.
        """
        with spans.span(spans.SERVE_SUBMIT, rid=req.rid):
            now = self._now()
            req.submitted_at = now
            if self._first_submit_t is None:
                self._first_submit_t = now
            if req.deadline_s is None:
                req.deadline_s = self.default_deadline_s
            self.counters["submitted"] += 1
            img = np.asarray(req.image, np.float32)
            if img.shape != self.image_shape:
                self._finish(req, "failed",
                             error=f"bad_request: image shape {img.shape} "
                                   f"!= {self.image_shape}")
                return req
            req.image = img
            if len(self.queue) >= self.queue_limit:
                self._finish(req, "overloaded",
                             error=f"queue full ({len(self.queue)}/"
                                   f"{self.queue_limit}); request shed")
                return req
            self.queue.append(req)
            return req

    def _finish(self, req: InferenceRequest, code: str, *,
                error: str | None = None, rung: str | None = None,
                completed_at: float | None = None) -> None:
        req.code = code
        req.error = error
        req.rung = rung
        req.completed_at = (completed_at if completed_at is not None
                            else self._now())
        self.counters[code] += 1
        if code == "ok":
            self._last_completion_t = req.completed_at
            if req.latency_s is not None:
                self.latencies.append(req.latency_s)
        else:
            _LOG.warning("[spectral-serve] request %s -> %s: %s",
                         req.rid, code, error)

    # -- load signal + ladder -----------------------------------------

    def _service_estimate_s(self) -> float | None:
        """Per-batch service-time estimate at the current load rung
        (EMA of observed batch wall times, injected slowness included),
        falling back to the worst known backend."""
        est = self._service_ema.get(SERVE_RUNGS[self._load_rung])
        if est is None and self._service_ema:
            est = max(self._service_ema.values())
        return est

    def _pressure(self, now: float) -> tuple[float, dict]:
        fill = (len(self.queue) / self.queue_limit
                if self.queue_limit else 0.0)
        risk = 0.0
        est = self._service_estimate_s()
        if self.queue and est is not None:
            at_risk = sum(
                1 for r in self.queue
                if r.deadline_s is not None
                and (r.submitted_at + r.deadline_s) - now < est)
            risk = at_risk / len(self.queue)
        p = min(1.0, max(fill, risk))
        return p, {"pressure": p, "queue_fill": fill,
                   "deadline_risk": risk, "queue_depth": len(self.queue)}

    def _transition(self, to_rung: int, direction: str, reason: str,
                    pressure: float) -> None:
        self.transitions.append({
            "tick": self._ticks, "t": self._now(),
            "direction": direction,
            "from": SERVE_RUNGS[self._load_rung],
            "to": SERVE_RUNGS[to_rung],
            "reason": reason, "pressure": pressure})
        if direction == "demote":
            self.n_demotions += 1
        else:
            self.n_promotions += 1
        _LOG.info("[spectral-serve] %s %s -> %s (%s)", direction,
                  SERVE_RUNGS[self._load_rung], SERVE_RUNGS[to_rung],
                  reason)
        self._load_rung = to_rung

    def _update_ladder(self, now: float) -> None:
        pressure, detail = self._pressure(now)
        self._last_pressure = detail
        if pressure >= self.demote_pressure:
            self._demote_streak += 1
            self._promote_streak = 0
        elif pressure <= self.promote_pressure:
            self._promote_streak += 1
            self._demote_streak = 0
        else:
            self._demote_streak = self._promote_streak = 0
        if (self._demote_streak >= self.demote_patience
                and self._load_rung < len(SERVE_RUNGS) - 1):
            self._transition(
                self._load_rung + 1, "demote",
                f"pressure {pressure:.2f} >= {self.demote_pressure} "
                f"for {self._demote_streak} tick(s)", pressure)
            self._demote_streak = 0
        elif self._promote_streak >= self.promote_patience \
                and self._load_rung > 0:
            target = self._load_rung - 1
            brk = self.breakers.get(SERVE_RUNGS[target])
            if brk is None or brk.allow():
                self._transition(
                    target, "promote",
                    f"pressure {pressure:.2f} <= "
                    f"{self.promote_pressure} for "
                    f"{self._promote_streak} tick(s)", pressure)
                self._promote_streak = 0
            # else: keep the streak; retry once the breaker cools down

    # -- batching ------------------------------------------------------

    def _bucket_for(self, n: int) -> int:
        for b in self.buckets:
            if b >= n:
                return b
        return self.max_bucket

    def _take_batch(self, now: float) -> list[InferenceRequest]:
        """Expire queued requests past their deadline, then pop up to
        ``max_bucket`` requests in FIFO order."""
        kept: collections.deque[InferenceRequest] = collections.deque()
        while self.queue:
            r = self.queue.popleft()
            if r.deadline_s is not None \
                    and now > r.submitted_at + r.deadline_s:
                self._finish(r, "deadline_exceeded",
                             error=f"deadline {r.deadline_s:.3f}s "
                                   f"exceeded before execution")
            else:
                kept.append(r)
        self.queue = kept
        batch = []
        while self.queue and len(batch) < self.max_bucket:
            batch.append(self.queue.popleft())
        return batch

    # -- plan fetch + variants ----------------------------------------

    def _fetch_plan(self, bucket: int):
        """Fetch the bucket's plan through the cache and the
        ``serve_plan_cache`` fault site; a fetched plan that fails
        ``validate_plan`` is never executed on an aggressive rung —
        the batch is forced onto the terminal einsum rung (which
        consumes only the pruned kernels, not the corrupt tables) and
        the corruption is counted + surfaced in ``health_report()``.

        Returns (plan, force_einsum).
        """
        plan = self.plans.get(self.params, self.cfg, bucket,
                              mesh_shape=self.mesh_shape,
                              **self.plan_kwargs)
        fetched = res.fault_corrupt("serve_plan_cache", plan,
                                    bucket=bucket)
        if fetched is not self._validated_plan.get(bucket):
            try:
                res.validate_plan(fetched, raise_on_error=True)
                self._validated_plan[bucket] = fetched
            except res.PlanValidationError as e:
                self.counters["plan_cache_corruptions"] += 1
                self._corrupt_buckets.add(bucket)
                _LOG.error("[spectral-serve] corrupt plan for bucket "
                           "%d; serving via einsum rung: %s", bucket,
                           str(e).splitlines()[0])
                return fetched, True
        self._corrupt_buckets.discard(bucket)
        return fetched, False

    def _variant(self, plan, bucket: int, rung: int):
        """The bucket's plan demoted to the given ladder rung (lazily
        derived via ``resilience.plan_at_backend_rung``, provenance
        stamped, cached per pristine plan object)."""
        ent = self._variants.get(bucket)
        if ent is None or ent["base"] is not plan:
            ent = {"base": plan, "rungs": {0: plan}}
            self._variants[bucket] = ent
        if rung not in ent["rungs"]:
            ent["rungs"][rung] = res.plan_at_backend_rung(
                plan, SERVE_RUNGS[rung],
                reason=f"load ladder rung {rung}")
        return ent["rungs"][rung]

    # -- execution -----------------------------------------------------

    def _note_service(self, backend: str, dt: float) -> None:
        prev = self._service_ema.get(backend)
        self._service_ema[backend] = (dt if prev is None
                                      else 0.5 * prev + 0.5 * dt)

    def _pad_batch(self, batch: list[InferenceRequest], bucket: int
                   ) -> np.ndarray:
        x = np.zeros((bucket,) + self.image_shape, np.float32)
        for i, req in enumerate(batch):
            x[i] = req.image
        return x

    def _upload(self, batch: list[InferenceRequest], bucket: int):
        """Start the (async) host->device copy of one padded batch; the
        double-buffered dispatch path consumes a copy started while the
        previous batch's kernels were still running."""
        key = (tuple(r.rid for r in batch), bucket)
        hit = self._staged is not None and self._staged["key"] == key
        with spans.span(spans.SERVE_UPLOAD, staged_hit=hit):
            if hit:
                self.counters["staged_hits"] += 1
                xj = self._staged["xj"]
            else:
                xj = jax.device_put(self._pad_batch(batch, bucket))
            self._staged = None
            return xj

    def _stage_next(self) -> None:
        """Peek (don't pop) the head of the queue and start uploading
        what the *next* tick will execute, overlapping the copy with
        the kernel currently in flight.  Best-effort: a stale stage is
        simply ignored by ``_upload``'s key check."""
        if not self.queue:
            return
        nxt = list(self.queue)[:self.max_bucket]
        bucket = self._bucket_for(len(nxt))
        key = (tuple(r.rid for r in nxt), bucket)
        if self._staged is not None and self._staged["key"] == key:
            return
        self._staged = {"key": key,
                        "xj": jax.device_put(self._pad_batch(nxt, bucket))}
        self.counters["staged_uploads"] += 1

    def _execute(self, batch: list[InferenceRequest], bucket: int
                 ) -> str | None:
        """Run one padded batch, walking ladder rungs from the current
        load rung down until one succeeds; returns the serving backend
        or None when even the terminal rung failed (requests then carry
        a ``failed`` response — still a terminal outcome)."""
        xj = self._upload(batch, bucket)
        with spans.span(spans.SERVE_PLAN):
            plan, force_einsum = self._fetch_plan(bucket)
        if force_einsum:
            order = [len(SERVE_RUNGS) - 1]
        else:
            order = list(range(self._load_rung, len(SERVE_RUNGS)))
        errors: list[str] = []
        for r in order:
            backend = SERVE_RUNGS[r]
            brk = self.breakers.get(backend)
            if brk is not None and not brk.allow():
                errors.append(f"{backend}: breaker open")
                continue
            try:
                res.fault_check("serve_kernel", backend=backend,
                                bucket=bucket)
                t0 = time.perf_counter()
                with spans.span(spans.SERVE_FORWARD):
                    if force_einsum:
                        y = cnn.forward_spectral(self.params, plan, xj,
                                                 backend="einsum")
                    else:
                        y = cnn.forward_spectral(
                            self.params, self._variant(plan, bucket, r),
                            xj, backend="pallas_fused",
                            interpret=self.interpret, guards=self.guards)
                # kernels are dispatched but not awaited: start the next
                # batch's upload now so the copy rides under them
                with spans.span(spans.SERVE_STAGE_NEXT):
                    self._stage_next()
                with spans.span(spans.SERVE_READBACK):
                    y = np.asarray(jax.block_until_ready(y))
                dt = time.perf_counter() - t0
            except Exception as e:      # noqa: BLE001 — isolation edge
                self.counters["kernel_faults"] += 1
                if brk is not None:
                    brk.record_failure(type(e).__name__)
                errors.append(f"{backend}: {type(e).__name__}: "
                              f"{str(e).splitlines()[0] if str(e) else ''}")
                _LOG.error("[spectral-serve] bucket %d failed on rung "
                           "%s: %s", bucket, backend, errors[-1])
                continue
            with spans.span(spans.SERVE_FINISH):
                extra = float(res.fault_corrupt("serve_slow", 0.0,
                                                backend=backend,
                                                bucket=bucket))
                if extra:
                    self.counters["slow_injections"] += 1
                    if hasattr(self.clock, "advance"):
                        self.clock.advance(extra)
                    dt += extra
                if brk is not None:
                    brk.record_success()
                self._note_service(backend, dt)
                done = self._now()
                for i, req in enumerate(batch):
                    req.logits = y[i]
                    self._finish(req, "ok", rung=backend,
                                 completed_at=done)
                self.served_by[backend] += len(batch)
                self.batches += 1
                return backend
        msg = "; ".join(errors) or "no execution rung available"
        for req in batch:
            self._finish(req, "failed", error=msg)
        return None

    # -- main loop -----------------------------------------------------

    def tick(self) -> int:
        """One serve step: expire deadlines, update the load ladder,
        form one bucket batch and execute it.  Returns the number of
        requests served a terminal outcome this tick."""
        with spans.span(spans.SERVE_TICK) as tick_span:
            self._ticks += 1
            now = self._now()
            with spans.span(spans.SERVE_TAKE):
                self._update_ladder(now)
                batch = self._take_batch(now)
            if not batch:
                return 0
            bucket = self._bucket_for(len(batch))
            rung = self._execute(batch, bucket)
            tick_span.set_metadata(rid=batch[0].rid, n=len(batch),
                                   bucket=bucket, rung=rung)
            return len(batch)

    def run_until_drained(self, max_ticks: int = 10_000,
                          cooldown_ticks: int | None = None) -> dict:
        """Tick until the queue drains (bounded by ``max_ticks``), then
        keep ticking up to ``cooldown_ticks`` idle steps so the ladder
        can promote back once pressure clears.  A tick that raises is a
        *loop death* — counted, the queue head is failed to guarantee
        progress, and the loop continues (the burst still drains)."""
        if cooldown_ticks is None:
            cooldown_ticks = 4 * self.promote_patience + 4
        ticks = 0
        while self.queue and ticks < max_ticks:
            try:
                self.tick()
            except Exception as e:      # noqa: BLE001 — loop must live
                self.loop_deaths += 1
                _LOG.exception("[spectral-serve] tick died: %s", e)
                if self.queue:
                    self._finish(self.queue.popleft(), "failed",
                                 error=f"loop exception: {e}")
            ticks += 1
        for _ in range(cooldown_ticks):
            if self._load_rung == 0 and all(
                    b.state == "closed" for b in self.breakers.values()):
                break
            try:
                self.tick()
            except Exception:           # noqa: BLE001
                self.loop_deaths += 1
            ticks += 1
        return self.stats()

    # -- reporting -----------------------------------------------------

    def stats(self) -> dict:
        lat = np.asarray(self.latencies, np.float64)
        out: dict = {
            "ticks": self._ticks,
            "batches": self.batches,
            "loop_deaths": self.loop_deaths,
            "queue_depth": len(self.queue),
            "counters": dict(self.counters),
            "served_by_rung": dict(self.served_by),
            "demotions": self.n_demotions,
            "promotions": self.n_promotions,
            "plan_phase_s": self.plans.stats()["phase_s"],
        }
        if lat.size:
            out["latency_ms"] = {
                "mean": float(lat.mean() * 1e3),
                "p50": float(np.percentile(lat, 50) * 1e3),
                "p95": float(np.percentile(lat, 95) * 1e3),
                "p99": float(np.percentile(lat, 99) * 1e3),
            }
        if (self._first_submit_t is not None
                and self._last_completion_t is not None):
            span = self._last_completion_t - self._first_submit_t
            if span > 0:
                out["throughput_img_s"] = self.counters["ok"] / span
        return out

    def health_report(self) -> dict:
        """Serve-level resilience status: the active rung, EVERY ladder
        transition (load demotions and promotions, with the pressure
        that drove them), breaker snapshots, queue/pressure state, the
        plan-cache counters and the per-bucket demotion provenance of
        the active plan variants."""
        plans = {}
        for bucket, ent in self._variants.items():
            active = ent["rungs"].get(self._load_rung, ent["base"])
            plans[f"bucket{bucket}"] = {
                "backends": sorted({lp.backend for lp in active.layers}),
                "demoted_layers": [lp.layer.name for lp in active.layers
                                   if lp.provenance],
                "provenance_sample": list(
                    active.layers[0].provenance),
            }
        return {
            "rung": SERVE_RUNGS[self._load_rung],
            "load_rung": self._load_rung,
            "pressure": dict(self._last_pressure),
            "transitions": list(self.transitions),
            "demotions": self.n_demotions,
            "promotions": self.n_promotions,
            "breakers": {n: b.snapshot()
                         for n, b in self.breakers.items()},
            "queue": {"depth": len(self.queue),
                      "limit": self.queue_limit},
            "counters": dict(self.counters),
            "plan_cache": {**self.plans.stats(),
                           "corrupt_buckets":
                               sorted(self._corrupt_buckets)},
            "plans": plans,
        }


def synthetic_requests(n: int, cfg, *, seed: int = 0,
                       deadline_s: float | None = None,
                       rid0: int = 0) -> list[InferenceRequest]:
    """Deterministic request batch for benchmarks/tests: seeded normal
    images at the config's input shape."""
    first = list(cfg.layers)[0]
    rng = np.random.default_rng(seed)
    return [InferenceRequest(
        rid=rid0 + i,
        image=rng.standard_normal(
            (first.c_in, first.h_in, first.w_in)).astype(np.float32),
        deadline_s=deadline_s)
        for i in range(n)]


def main() -> int:
    """Serve a synthetic burst; exit status 1 when any request ``failed``
    or a tick of the serve loop died (shed and deadline-expired
    requests are the server's designed outcomes, not failures)."""
    from repro.configs import vgg16_spectral
    from repro.launch.compile_cache import enable_compile_cache

    ap = argparse.ArgumentParser(
        description="overload-resilient spectral serving front end "
                    "(synthetic burst driver)")
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--queue-limit", type=int, default=8)
    ap.add_argument("--buckets", type=int, nargs="+",
                    default=list(DEFAULT_BUCKETS))
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-request deadline (default: unlimited)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--json", default=None,
                    help="write stats+health JSON to this path "
                         "('-' for stdout)")
    args = ap.parse_args()
    enable_compile_cache()

    srv = SpectralServer(vgg16_spectral.SMOKE, buckets=args.buckets,
                         queue_limit=args.queue_limit, seed=args.seed,
                         default_deadline_s=(
                             args.deadline_ms / 1e3
                             if args.deadline_ms is not None else None))
    reqs = synthetic_requests(args.requests, srv.cfg, seed=args.seed)
    for r in reqs:
        srv.submit(r)
    stats = srv.run_until_drained()
    health = srv.health_report()
    print(f"[spectral-serve] {args.requests} requests -> "
          f"{stats['counters']['ok']} ok / "
          f"{stats['counters']['overloaded']} shed / "
          f"{stats['counters']['deadline_exceeded']} deadline / "
          f"{stats['counters']['failed']} failed in "
          f"{stats['ticks']} ticks on rung {health['rung']} "
          f"({stats['demotions']} demotions, "
          f"{stats['promotions']} promotions)")
    if "latency_ms" in stats:
        lm = stats["latency_ms"]
        print(f"[spectral-serve] latency ms p50 {lm['p50']:.1f} "
              f"p95 {lm['p95']:.1f} p99 {lm['p99']:.1f}; throughput "
              f"{stats.get('throughput_img_s', float('nan')):.1f} img/s")
    if args.json:
        payload = json.dumps({"stats": stats, "health": health},
                             indent=2)
        if args.json == "-":
            print(payload)
        else:
            with open(args.json, "w") as f:
                f.write(payload + "\n")
    failed = stats["counters"]["failed"] + stats["loop_deaths"]
    if failed:
        print(f"[spectral-serve] FAILED: {stats['counters']['failed']} "
              f"failed request(s), {stats['loop_deaths']} loop death(s)",
              file=sys.stderr)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
